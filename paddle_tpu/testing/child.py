"""Child processes for tests: every wait has a deadline and no orphan is left.

A test that starts a child reads its output through ``Child``: the pipes are
drained by threads, so waiting for a line (``wait_for``) or for the exit
(``wait``) gives up at its deadline whether or not the child ever prints, and
leaving the ``with`` block kills the child's whole process group, the
launcher's ranks and a supervisor's workers included.
"""
from __future__ import annotations

import os
import re
import signal
import subprocess
import sys
import threading
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# A child's default limit. Below the per-test limit of tests/conftest.py, so
# that a stuck child fails its own test with its output and not the worker.
CHILD_LIMIT_S = 150.0


def cpu_env(**extra: str) -> dict:
    """This process's environment with JAX held to the CPU (a chip belongs to
    one process at a time) and the repo importable from any directory."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


class Child:
    """``argv`` running in a process group of its own, stdout and stderr kept
    apart as text."""

    def __init__(self, argv, *, env=None, cwd=None):
        self.argv = list(argv)
        self._proc = subprocess.Popen(
            self.argv, env=env, cwd=cwd, stdin=subprocess.DEVNULL,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            errors="replace", start_new_session=True)
        self._lines = {"stdout": [], "stderr": []}
        self._grew = threading.Condition()
        self._readers = [
            threading.Thread(target=self._drain, args=(pipe, name),
                             daemon=True)
            for pipe, name in ((self._proc.stdout, "stdout"),
                               (self._proc.stderr, "stderr"))]
        for reader in self._readers:
            reader.start()

    def _drain(self, pipe, name):
        for line in pipe:
            with self._grew:
                self._lines[name].append(line)
                self._grew.notify_all()
        with self._grew:
            self._grew.notify_all()

    @property
    def pid(self) -> int:
        return self._proc.pid

    @property
    def stdout(self) -> str:
        return "".join(self._lines["stdout"])

    @property
    def stderr(self) -> str:
        return "".join(self._lines["stderr"])

    def _said(self) -> str:
        return (f"{' '.join(self.argv)}\n--- stdout ---\n{self.stdout}"
                f"--- stderr ---\n{self.stderr}")

    def wait_for(self, pattern: str, timeout: float) -> re.Match:
        """The first match of ``pattern`` in a line of either stream; raises
        ``TimeoutError`` with what the child said when ``timeout`` seconds
        pass, or the child ends, without one."""
        deadline = time.monotonic() + timeout
        seen = {"stdout": 0, "stderr": 0}
        with self._grew:
            while True:
                for name, lines in self._lines.items():
                    for line in lines[seen[name]:]:
                        match = re.search(pattern, line)
                        if match:
                            return match
                    seen[name] = len(lines)
                left = deadline - time.monotonic()
                ended = not any(r.is_alive() for r in self._readers)
                if left <= 0 or ended:
                    raise TimeoutError(
                        f"no line matching {pattern!r} "
                        f"{'before the child ended' if ended else f'in {timeout} s'}"
                        f": {self._said()}")
                self._grew.wait(min(left, 1.0))

    def wait(self, timeout: float = CHILD_LIMIT_S) -> int:
        """The exit code; a child still running after ``timeout`` seconds is
        killed with its group and raises ``subprocess.TimeoutExpired``."""
        try:
            code = self._proc.wait(timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise subprocess.TimeoutExpired(
                self.argv, timeout, self.stdout, self.stderr) from None
        for reader in self._readers:    # to the end of what it wrote
            reader.join(5.0)
        return code

    def stop(self, sig: int = signal.SIGINT, timeout: float = 30.0) -> int:
        """Sends ``sig`` to the child and waits for it as ``wait`` does."""
        if self._proc.poll() is None:
            self._proc.send_signal(sig)
        return self.wait(timeout)

    def kill(self) -> None:
        """SIGKILL to every process of the child's group, and reaps it."""
        try:
            os.killpg(self._proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self._proc.wait()

    def __enter__(self) -> "Child":
        return self

    def __exit__(self, *exc) -> None:
        self.kill()
        for reader in self._readers:
            reader.join(5.0)
        self._proc.stdout.close()
        self._proc.stderr.close()


def run_child(argv, *, env=None, cwd=None,
              timeout: float = CHILD_LIMIT_S) -> subprocess.CompletedProcess:
    """``subprocess.run(argv, capture_output=True, text=True)`` that also
    takes the child's descendants with it, at the end and at ``timeout``."""
    with Child(argv, env=env, cwd=cwd) as child:
        code = child.wait(timeout)
        return subprocess.CompletedProcess(child.argv, code, child.stdout,
                                           child.stderr)


def run_launch(tmp_path, script_body: str, launch_args=(), script_args=(),
               *, timeout: float = CHILD_LIMIT_S):
    """``python -m paddle_tpu.distributed.launch`` over ``script_body``
    (written to ``tmp_path/companion.py``), logs under ``tmp_path/log``."""
    script = tmp_path / "companion.py"
    script.write_text(script_body)
    return run_child(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--log_dir", str(tmp_path / "log"), *launch_args, str(script),
         *script_args],
        env=cpu_env(), cwd=REPO_ROOT, timeout=timeout)
