"""python -m paddle_tpu.distributed.launch — the launcher CLI.

Parity: python/paddle/distributed/launch/ (collective controller): builds the
job context, spawns one process per host-slot with the PADDLE_*/JAX_* env
contract, captures per-rank logs (workerlog.N), restarts on failure up to
--max_restart (elastic semantics; SURVEY §5.3).

TPU-native: one process per HOST (not per chip) — inside each process JAX owns
all local chips; rendezvous is the JAX coordination service, not TCPStore.
A chip belongs to one process at a time and the launcher does not partition
chips between children, so --nproc_per_node > 1 is refused on a TPU host
(it stays the way to run CPU ranks: JAX_PLATFORMS=cpu). The launcher itself
never initialises a JAX backend: a parent that has would hold the chips its
child needs.

Gang supervision (SURVEY §5.3 failure detection): children are POLLED, not
serially wait()ed — the first non-zero exit (a crash, or a watchdog-initiated
exit on a survivor) triggers SIGTERM -> grace -> SIGKILL of the whole gang, a
per-rank failure report (exit code + the failing rank's workerlog tail), and
an exponential-backoff restart with a FRESH master port and
PADDLE_RESTART_COUNT bumped (the elastic generation number — training
companions resume via distributed.checkpoint.load_latest). Each generation
logs to workerlog.N.restartK so post-mortems never interleave generations.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time

from ..logjson import log_event


def _local_tpu_present() -> bool:
    """TPU device nodes on this host, found WITHOUT touching JAX."""
    import glob
    return bool(glob.glob("/dev/accel[0-9]*")
                or glob.glob("/dev/vfio/[0-9]*"))


def _refuse_shared_chips(nproc_per_node: int, env) -> str | None:
    """The refusal message when several children of one host would each
    try to claim the same TPU chips (all but one fail or hang), else
    None. Children pinned off the TPU (JAX_PLATFORMS=cpu) are fine."""
    if nproc_per_node <= 1 or env.get("JAX_PLATFORMS", "") == "cpu" \
            or not _local_tpu_present():
        return None
    return (f"paddle_tpu.distributed.launch: --nproc_per_node="
            f"{nproc_per_node} on a TPU host: a chip belongs to ONE "
            "process at a time and the launcher does not partition chips "
            "between children. One process driving all local chips "
            "(--nproc_per_node 1, the default) is the supported "
            "single-host mode; for CPU ranks set JAX_PLATFORMS=cpu.")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _log_path(log_dir: str, rank: int, attempt: int) -> str:
    """Generation-rotated per-rank log: attempt 0 keeps the classic
    workerlog.N name, restarts get workerlog.N.restartK."""
    name = f"workerlog.{rank}" if attempt == 0 \
        else f"workerlog.{rank}.restart{attempt}"
    return os.path.join(log_dir, name)


def _tail(path: str, n: int = 20) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - 8192))
            lines = f.read().decode("utf-8", "replace").splitlines()
        return "\n".join(lines[-n:])
    except OSError:
        return "<no log captured>"


def _reap_gang(procs, grace_s: float):
    """SIGTERM every still-running child, give them `grace_s` to unwind
    (flush logs, close stores), then SIGKILL the stragglers. Returns the
    final exit codes (None never: everyone is dead on return)."""
    for p in procs:
        if p.poll() is None:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
    deadline = time.time() + grace_s
    while time.time() < deadline and any(p.poll() is None for p in procs):
        time.sleep(0.1)
    for p in procs:
        if p.poll() is None:
            try:
                p.kill()
            except OSError:
                pass
            p.wait()
    return [p.poll() for p in procs]


def _spawn_gang(args, master, attempt):
    nprocs = args.nproc_per_node
    world = nprocs * args.nnodes
    procs, logs = [], []
    try:
        for local_rank in range(nprocs):
            rank = args.node_rank * nprocs + local_rank
            env = dict(os.environ)
            env.update({
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "PADDLE_LOCAL_RANK": str(local_rank),
                "PADDLE_MASTER": master,
                "PADDLE_CURRENT_ENDPOINT": f"127.0.0.1:{_free_port()}",
                "PADDLE_RESTART_COUNT": str(attempt),
                "JAX_PROCESS_ID": str(rank),
                "JAX_NUM_PROCESSES": str(world),
                "JAX_COORDINATOR_ADDRESS": master,
            })
            # flight dumps land next to the workerlogs unless the user
            # pinned a dir — the supervisor's failure report aggregates
            # flightdump.<rank>.<generation>.json from here
            env.setdefault("PADDLE_FLIGHT_DUMP_DIR", args.log_dir)
            logf = open(_log_path(args.log_dir, rank, attempt), "a")
            logs.append(logf)
            # every rank INCLUDING 0 logs to its workerlog: rank 0 hosts
            # the store daemon and is the most failure-prone rank — the
            # failure report must be able to tail its log too
            p = subprocess.Popen(
                [sys.executable, args.training_script] +
                args.training_script_args,
                env=env, stdout=logf, stderr=subprocess.STDOUT)
            p._pd_rank = rank
            procs.append(p)
    except Exception:
        # a mid-loop spawn failure (EMFILE, ENOMEM) must not strand the
        # already-started ranks holding the rendezvous ports
        _reap_gang(procs, getattr(args, "grace_period", 5.0))
        for f in logs:
            f.close()
        raise
    return procs, logs


def _emit_flight_diagnosis(args, attempt, world, stream=None):
    """Aggregate the generation's flight dumps into the cross-rank
    desync verdict and emit it as a ``gang_diagnosis`` event (plain
    mode prints the diagnosis text verbatim — the SAME text
    ``tools/flight_report.py`` prints offline, byte-for-byte; JSON mode
    carries the structured fields for machine ingestion). Ranks whose
    dump is missing or unparsable (crashed before dumping) are NAMED in
    the diagnosis instead of silently omitted. Returns the struct, or
    None when no dumps exist (recorder disabled)."""
    from ..resilience import flight_recorder
    dump_dir = os.environ.get("PADDLE_FLIGHT_DUMP_DIR") or args.log_dir
    # only the ranks THIS supervisor spawned can be expected to dump
    # into this node's dir — remote nodes' ranks dump on their hosts
    local = [args.node_rank * args.nproc_per_node + i
             for i in range(args.nproc_per_node)]
    try:
        text, diag = flight_recorder.diagnose_dir(
            dump_dir, world=world, generation=attempt,
            expected_ranks=local)
    except Exception as e:          # a broken dump must not mask the
        log_event("launch", "gang_diagnosis_error", stream=stream,
                  message=f"launch: flight diagnosis failed: {e!r}",
                  generation=attempt, error=repr(e))
        return None                 # underlying failure report
    if not diag["ranks_with_dump"] and not diag["missing_dump_errors"]:
        return None                 # no recorder output for this gang
    log_event("launch", "gang_diagnosis", stream=stream, message=text,
              generation=attempt, world=world, desync=diag["desync"],
              stragglers=diag["stragglers"], stuck=diag["stuck"],
              ranks_with_dump=diag["ranks_with_dump"],
              ranks_missing_dump=diag["ranks_missing_dump"],
              missing_dump_errors=diag["missing_dump_errors"],
              groups=diag["groups"])
    return diag


def _failure_report(args, procs, attempt) -> str:
    lines = [f"launch: gang failure report (attempt {attempt}):"]
    for p in procs:
        rc = p.poll()
        rank = p._pd_rank
        status = "ok" if rc == 0 else (
            f"signal {-rc}" if rc is not None and rc < 0 else f"exit {rc}")
        lines.append(f"launch:   rank {rank}: {status}")
        if rc not in (0, None):
            tail = _tail(_log_path(args.log_dir, rank, attempt))
            lines.append(f"launch:   --- workerlog tail (rank {rank}) ---")
            lines.extend(f"launch:   | {ln}" for ln in tail.splitlines())
    return "\n".join(lines)


def main():
    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--nproc_per_node", "--nprocs", type=int, default=1)
    parser.add_argument("--nnodes", type=int, default=1)
    parser.add_argument("--node_rank", type=int, default=0)
    parser.add_argument("--master", default=None)
    parser.add_argument("--log_dir", default="log")
    parser.add_argument("--max_restart", type=int, default=0)
    parser.add_argument(
        "--restart_backoff", type=float,
        default=float(os.environ.get("PADDLE_RESTART_BACKOFF_S", "1")),
        help="base of the exponential restart backoff (seconds)")
    parser.add_argument(
        "--grace_period", type=float,
        default=float(os.environ.get("PADDLE_LAUNCH_GRACE_S", "5")),
        help="SIGTERM->SIGKILL grace when tearing down a failed gang")
    parser.add_argument("--devices", "--gpus", default=None,
                        help="accepted for reference-CLI parity and "
                             "ignored: each process owns all the chips "
                             "it can see")
    parser.add_argument("--job_id", default="default")
    parser.add_argument("training_script")
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args()

    refusal = _refuse_shared_chips(args.nproc_per_node, os.environ)
    if refusal:
        print(refusal, file=sys.stderr)
        sys.exit(2)
    os.makedirs(args.log_dir, exist_ok=True)
    poll_s = float(os.environ.get("PADDLE_LAUNCH_POLL_S", "0.2"))
    backoff_cap = float(os.environ.get("PADDLE_RESTART_BACKOFF_MAX_S", "30"))

    attempt = 0
    while True:
        # fresh master port per generation (unless pinned by --master):
        # the previous generation's coordinator/TCPStore sockets may
        # linger in TIME_WAIT, and a stale store daemon must never serve
        # the new generation's rendezvous
        master = args.master or f"127.0.0.1:{_free_port()}"
        procs, logs = _spawn_gang(args, master, attempt)
        # JSON-only event (no plain-mode print existed here): the
        # cluster front-end sees each generation start with its master
        log_event("launch", "gang_start", stream=sys.stderr,
                  generation=attempt, master=master,
                  world=args.nproc_per_node * args.nnodes,
                  pids=[p.pid for p in procs])
        first_bad = None
        try:
            while True:
                codes = [p.poll() for p in procs]
                bad = [p for p in procs
                       if p.poll() not in (0, None)]
                if bad:
                    first_bad = bad[0]
                    break
                if all(c == 0 for c in codes):
                    return 0
                time.sleep(poll_s)
            # gang failure: tear down the survivors, then report
            _reap_gang(procs, args.grace_period)
        except KeyboardInterrupt:
            _reap_gang(procs, args.grace_period)
            raise
        finally:
            for f in logs:
                f.close()

        fail_rc = first_bad.poll()
        fail_rc = fail_rc if fail_rc > 0 else 128 - fail_rc  # signal -> 128+N
        log_event("launch", "gang_failure", stream=sys.stderr,
                  message=_failure_report(args, procs, attempt),
                  generation=attempt, failed_rank=first_bad._pd_rank,
                  failed_rc=fail_rc,
                  exit_codes={p._pd_rank: p.poll() for p in procs},
                  log_tail=_tail(_log_path(args.log_dir,
                                           first_bad._pd_rank, attempt)))
        # cross-rank flight diagnosis: name the desynced collective and
        # the straggler rank instead of leaving only the log tail
        _emit_flight_diagnosis(args, attempt,
                               args.nproc_per_node * args.nnodes,
                               stream=sys.stderr)
        attempt += 1
        if attempt > args.max_restart:
            log_event("launch", "restart_budget_exhausted",
                      stream=sys.stderr,
                      message=f"launch: rank {first_bad._pd_rank} failed "
                              f"(rc {fail_rc}); restart budget exhausted "
                              f"({args.max_restart})",
                      generation=attempt - 1,
                      failed_rank=first_bad._pd_rank, failed_rc=fail_rc,
                      max_restart=args.max_restart)
            return fail_rc
        delay = min(args.restart_backoff * (2 ** (attempt - 1)),
                    backoff_cap)
        log_event("launch", "restart", stream=sys.stderr,
                  message=f"launch: restarting (attempt {attempt}/"
                          f"{args.max_restart}) after {delay:.1f}s "
                          f"backoff, fresh master port, "
                          f"PADDLE_RESTART_COUNT={attempt}",
                  generation=attempt, backoff_s=round(delay, 3),
                  max_restart=args.max_restart)
        time.sleep(delay)


if __name__ == "__main__":
    sys.exit(main())
