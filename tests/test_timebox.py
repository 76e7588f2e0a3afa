"""The per-test limit of ``tests/conftest.py`` works: a child pytest run under
xdist, with the limit patched to a few seconds, over a test that loops in
Python, one that waits outside the interpreter, one whose fixture's finalizer
waits, and tests that pass. And the shared child-process helper gives up on a
child that says nothing."""
import os
import re
import subprocess
import sys
import time

import pytest

from paddle_tpu.testing.child import Child, cpu_env, run_child

TESTS = os.path.dirname(os.path.abspath(__file__))
LIMIT = 4.0

CONFTEST = f"""
import importlib.util
spec = importlib.util.spec_from_file_location(
    "tier1_conftest", {os.path.join(TESTS, "conftest.py")!r})
tier1_conftest = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tier1_conftest)
tier1_conftest.LIMIT = {LIMIT}
globals().update((name, hook) for name, hook in vars(tier1_conftest).items()
                 if name.startswith("pytest_"))   # the hooks, no fixture
"""

STUCK = {
    "in_python": """
import time
def test_stuck():
    while True:
        time.sleep(0.05)
""",
    # no signal handler runs and no bytecode: the second lock of a plain
    # mutex by its own thread waits for good, with the GIL released
    "outside_python": """
import ctypes
def test_stuck():
    libc = ctypes.CDLL(None)
    mutex = ctypes.create_string_buffer(128)
    assert libc.pthread_mutex_init(mutex, None) == 0
    assert libc.pthread_mutex_lock(mutex) == 0
    libc.pthread_mutex_lock(mutex)
""",
    "in_finalizer": """
import threading
import pytest
@pytest.fixture
def held():
    yield
    threading.Event().wait()
def test_stuck(held):
    pass
""",
}

PASSING = """
import pytest
@pytest.mark.parametrize("i", range(4))
def test_fine(i):
    assert i >= 0
"""


@pytest.fixture(scope="module")
def child_run(tmp_path_factory):
    """(seconds, exit code, stdout, stderr) of one child run over all of the
    above."""
    root = tmp_path_factory.mktemp("timebox")
    (root / "conftest.py").write_text(CONFTEST)
    for name, body in STUCK.items():
        (root / f"test_stuck_{name}.py").write_text(body)
    for i in range(2):
        (root / f"test_passing_{i}.py").write_text(PASSING)
    start = time.monotonic()
    r = run_child(
        [sys.executable, "-m", "pytest", str(root), "-q", "-p", "xdist",
         "-n", "2", "--dist", "loadfile", "-p", "no:cacheprovider",
         "-p", "no:randomly"],
        env=cpu_env(), cwd=str(root), timeout=120)
    return time.monotonic() - start, r.returncode, r.stdout, r.stderr


@pytest.mark.parametrize("where", sorted(STUCK))
def test_a_stuck_test_fails_by_name_with_its_stacks(child_run, where):
    _, _, report, stacks = child_run
    name = f"test_stuck_{where}.py::test_stuck"
    assert re.search(rf"FAILED {re.escape(name)}", report), report
    assert re.search(rf"crashed while running .*{re.escape(name)}",
                     report), report
    # the watchdog's dump, on the real stderr: the waiting frame's file and
    # function (two workers may write at once, so no more of the line)
    assert f"test_stuck_{where}.py" in stacks, stacks
    assert ("held" if where == "in_finalizer" else "test_stuck") in stacks


def test_the_other_tests_still_pass_and_the_run_ends(child_run):
    seconds, code, report, stacks = child_run
    assert code == 1, report + stacks
    # 8 and the call of the test whose finalizer waits
    assert re.search(r"3 failed, 9 passed", report), report
    assert stacks.count("Timeout (0:00:04)!") == 3, stacks
    assert len(re.findall(r"\[gw\d+\] node down", report)) == 3, report
    # three limits, one after the other at worst, and the workers' starts
    assert seconds < 3 * LIMIT + 60


def test_the_helper_gives_up_on_a_silent_child():
    silent = [sys.executable, "-c", "import time; time.sleep(600)"]
    with Child(silent) as child:
        start = time.monotonic()
        with pytest.raises(TimeoutError, match="no line matching 'ready'"):
            child.wait_for("ready", timeout=1.0)
        assert time.monotonic() - start < 5.0
        with pytest.raises(subprocess.TimeoutExpired):
            child.wait(timeout=0.5)
        pid = child.pid
    with pytest.raises(ProcessLookupError):     # killed and reaped
        os.kill(pid, 0)


def test_the_helper_takes_the_grandchildren_too(tmp_path):
    pidfile = tmp_path / "pid"
    code = ("import subprocess, sys, time\n"
            "p = subprocess.Popen([sys.executable, '-c', "
            "'import time; time.sleep(600)'])\n"
            f"open({str(pidfile)!r}, 'w').write(str(p.pid))\n"
            "print('ready', flush=True)\n"
            "time.sleep(600)\n")
    with Child([sys.executable, "-c", code]) as child:
        child.wait_for("ready", timeout=30.0)
        grandchild = int(pidfile.read_text())
        os.kill(grandchild, 0)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:          # init reaps it
        try:
            os.kill(grandchild, 0)
        except ProcessLookupError:
            return
        time.sleep(0.05)
    state = open(f"/proc/{grandchild}/stat").read().split()[2]
    assert state == "Z", f"grandchild {grandchild} still runs ({state})"
