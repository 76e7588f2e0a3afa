"""Elastic fault-tolerant runtime (ISSUE 3; SURVEY §5.3): heartbeat
watchdog, monitored barrier, fault-injection harness, auto-resume
checkpoints, and the gang supervisor end-to-end.

Every wait here is BOUNDED (subprocess timeouts, deadline loops): no test
in this file may hang tier-1. Multi-process cases ride the fast gloo CPU
path; PADDLE_FI_* vars are only ever set in COMPANION subprocess envs (or
this file's own monkeypatched process — see the conftest leak guard).
"""
import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.native import TCPStore, TCPStoreServer, load_native
from paddle_tpu.distributed.checkpoint import (latest_step, load_latest,
                                               save_checkpoint,
                                               wait_all_async_saves)
from paddle_tpu.distributed.resilience import (PeerFailureError, Watchdog,
                                               WATCHDOG_EXIT_CODE)
from paddle_tpu.testing import FI_ENV_VARS, fault
from paddle_tpu.testing.child import (REPO_ROOT, cpu_env, run_child,
                                      run_launch)
from paddle_tpu.tensor.tensor import Tensor

needs_native = pytest.mark.skipif(load_native() is None,
                                  reason="native runtime unavailable")


# =====================================================================
# Watchdog: dropped heartbeat -> PeerFailureError within the timeout
# =====================================================================
@needs_native
class TestWatchdog:
    def _mk(self, srv, rank, world, timeout_s=1.0):
        return Watchdog(lambda t: TCPStore("127.0.0.1", srv.port,
                                           timeout_s=t),
                        rank, world, timeout_s=timeout_s,
                        interval_s=0.1, action="flag")

    def _await_failure(self, wd, bound_s=8.0):
        deadline = time.monotonic() + bound_s
        while wd.failure is None and time.monotonic() < deadline:
            time.sleep(0.05)
        return wd.failure

    def test_dropped_heartbeat_flags_peer_within_timeout(self):
        srv = TCPStoreServer(0)
        wd0 = wd1 = None
        try:
            wd0 = self._mk(srv, 0, 2).start()
            wd1 = self._mk(srv, 1, 2).start()
            time.sleep(0.6)
            assert wd0.failure is None and wd1.failure is None  # healthy
            t_drop = time.monotonic()
            wd1.stop()                          # rank 1 goes dark
            err = self._await_failure(wd0)
            latency = time.monotonic() - t_drop
            assert isinstance(err, PeerFailureError), err
            assert err.ranks == (1,)
            assert "rank" in str(err) and "heartbeat" in str(err)
            # detection latency ~ timeout, not unbounded; generous slack
            # for a loaded CI box but far below "hangs forever"
            assert latency < 6.0, latency
            with pytest.raises(PeerFailureError):
                wd0.check()
        finally:
            for wd in (wd0, wd1):
                if wd is not None:
                    wd.stop()
            srv.stop()

    def test_peer_that_never_started_is_named(self):
        srv = TCPStoreServer(0)
        wd0 = None
        try:
            wd0 = self._mk(srv, 0, 2).start()
            err = self._await_failure(wd0)
            assert isinstance(err, PeerFailureError)
            assert err.ranks == (1,)
        finally:
            if wd0 is not None:
                wd0.stop()
            srv.stop()

    def test_store_death_unwedges_survivor(self):
        srv = TCPStoreServer(0)
        wd0 = self._mk(srv, 0, 2)
        try:
            wd0.start()
            time.sleep(0.3)
            srv.stop()                  # coordinator host "dies"
            err = self._await_failure(wd0)
            assert isinstance(err, PeerFailureError)
            # when the whole store vanishes there is no single guilty rank
            assert err.ranks == ()
            assert "store" in str(err)
        finally:
            wd0.stop()

    def test_clean_exit_marker_exempts_departed_peer(self):
        """A rank that FINISHES stops beating too — its wd/done marker
        must read as departure, not death (else every job whose ranks
        finish at different times ends in a spurious failure report)."""
        srv = TCPStoreServer(0)
        wd0 = wd1 = None
        try:
            wd0 = self._mk(srv, 0, 2).start()
            wd1 = self._mk(srv, 1, 2).start()
            time.sleep(0.4)
            wd1.mark_clean_exit()
            wd1.stop()              # rank 1 departs CLEANLY
            time.sleep(3.0)         # well past timeout_s=1.0
            assert wd0.failure is None
        finally:
            for wd in (wd0, wd1):
                if wd is not None:
                    wd.stop()
            srv.stop()

    def test_store_retirement_after_clean_departures_is_benign(self):
        """The TCPStore daemon rides rank 0's process, so a coordinator
        that FINISHES takes the store with it. A survivor whose watcher
        already cached every peer's done marker must treat the vanished
        store as job teardown, not 'coordinator host presumed dead'."""
        srv = TCPStoreServer(0)
        wd1 = None
        try:
            wd1 = self._mk(srv, 1, 2).start()
            c = TCPStore("127.0.0.1", srv.port, timeout_s=2.0)
            c.set("wd/done/0", b"1")    # rank 0 departs cleanly...
            c.close()
            time.sleep(0.5)             # watcher caches the marker
            srv.stop()                  # ...and retires its store daemon
            time.sleep(3.0)             # well past timeout_s=1.0
            assert wd1.failure is None
        finally:
            if wd1 is not None:
                wd1.stop()
            srv.stop()

    def test_store_retirement_with_peers_still_running(self):
        """world=3, coordinator departed cleanly, rank 2 still mid-epoch:
        rank 1 cannot judge anyone without a store — retire, don't
        declare the coordinator dead and tear down a healthy rank."""
        srv = TCPStoreServer(0)
        wd1 = None
        try:
            wd1 = Watchdog(lambda t: TCPStore("127.0.0.1", srv.port,
                                              timeout_s=t),
                           1, 3, timeout_s=1.0, interval_s=0.1,
                           action="flag").start()
            c = TCPStore("127.0.0.1", srv.port, timeout_s=2.0)
            c.set("wd/done/0", b"1")    # rank 0 departs cleanly
            c.close()
            time.sleep(0.5)             # watcher caches the marker
            srv.stop()                  # store retires with rank 0
            time.sleep(3.0)
            assert wd1.failure is None
        finally:
            if wd1 is not None:
                wd1.stop()
            srv.stop()

    def test_crashed_rank_posts_no_done_marker(self):
        """atexit fires on uncaught-exception deaths too — a crashing
        rank must NOT exempt itself from staleness (survivors would
        wedge waiting on it in the next collective)."""
        srv = TCPStoreServer(0)
        try:
            wd = self._mk(srv, 0, 2)
            wd._crashed = True
            wd.mark_clean_exit()        # must refuse to post
            wd2 = self._mk(srv, 1, 2)
            wd2.failure = PeerFailureError("peer already failed")
            wd2.mark_clean_exit()       # exiting DUE to failure: same
            c = TCPStore("127.0.0.1", srv.port, timeout_s=2.0)
            assert c.get("wd/done/0") is None
            assert c.get("wd/done/1") is None
            c.close()
        finally:
            srv.stop()

    def test_require_progress_converts_main_thread_stall(self, monkeypatch):
        """PADDLE_WATCHDOG_REQUIRE_PROGRESS_S: a wedged MAIN thread
        (publisher daemon still alive — the collective-hang case the
        default mode cannot see) goes dark and the peer flags it."""
        monkeypatch.setenv("PADDLE_WATCHDOG_REQUIRE_PROGRESS_S", "0.4")
        srv = TCPStoreServer(0)
        wd0 = wd1 = None
        try:
            wd0 = self._mk(srv, 0, 2).start()
            wd1 = self._mk(srv, 1, 2).start()
            for _ in range(6):              # both "stepping": healthy
                wd0.notify_progress()
                wd1.notify_progress()
                time.sleep(0.1)
            assert wd0.failure is None and wd1.failure is None
            # rank 1's main thread wedges: no more notify_progress, but
            # its publisher thread keeps running
            deadline = time.monotonic() + 8.0
            while wd0.failure is None and time.monotonic() < deadline:
                wd0.notify_progress()
                time.sleep(0.05)
            err = wd0.failure
            assert isinstance(err, PeerFailureError), err
            assert err.ranks == (1,)
        finally:
            for wd in (wd0, wd1):
                if wd is not None:
                    wd.stop()
            srv.stop()

    def test_fault_injected_heartbeat_drop(self, monkeypatch):
        """PADDLE_FI_DROP_HEARTBEAT silences exactly the targeted rank's
        publisher; the PEER's watchdog converts that into the error."""
        monkeypatch.setenv("PADDLE_FI_DROP_HEARTBEAT", "1")
        srv = TCPStoreServer(0)
        wd0 = wd1 = None
        try:
            wd0 = self._mk(srv, 0, 2).start()
            wd1 = self._mk(srv, 1, 2).start()   # publisher injected dark
            err = self._await_failure(wd0)
            assert isinstance(err, PeerFailureError)
            assert err.ranks == (1,)
            # rank 1 itself keeps watching rank 0 just fine
            assert wd1.failure is None
        finally:
            for wd in (wd0, wd1):
                if wd is not None:
                    wd.stop()
            srv.stop()


class TestPeerFailureContract:
    """Process-local contracts (no native runtime needed)."""

    def test_zero_arg_instantiable_for_async_raise(self):
        # PyThreadState_SetAsyncExc is handed the CLASS; the main
        # thread's exception normalization instantiates it with no
        # arguments — a required positional would surface as TypeError
        # and `except PeerFailureError` handlers would never match
        err = PeerFailureError()
        assert isinstance(err, RuntimeError)
        assert err.ranks == ()
        assert "current_watchdog" in str(err)

    def test_module_barrier_refuses_silent_noop(self, monkeypatch):
        from paddle_tpu.distributed import resilience
        if resilience.current_watchdog() is not None:
            pytest.skip("a global watchdog is running in this process")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
        with pytest.raises(RuntimeError, match="no watchdog"):
            resilience.monitored_barrier()

    def test_module_barrier_single_process_trivial(self, monkeypatch):
        from paddle_tpu.distributed import resilience
        if resilience.current_watchdog() is not None:
            pytest.skip("a global watchdog is running in this process")
        monkeypatch.delenv("PADDLE_TRAINERS_NUM", raising=False)
        resilience.monitored_barrier()      # trivially satisfied


# =====================================================================
# monitored_barrier: names the missing rank instead of wedging
# =====================================================================
@needs_native
class TestMonitoredBarrier:
    def test_missing_rank_is_named(self):
        srv = TCPStoreServer(0)
        try:
            wd0 = Watchdog(lambda t: TCPStore("127.0.0.1", srv.port,
                                       timeout_s=t),
                           0, 2, timeout_s=1.0, interval_s=0.1,
                           action="flag")
            t0 = time.monotonic()
            with pytest.raises(PeerFailureError) as ei:
                wd0.monitored_barrier(timeout_s=1.0, tag="t1")
            assert ei.value.ranks == (1,)
            assert time.monotonic() - t0 < 6.0
        finally:
            srv.stop()

    def test_nonzero_rank_times_out_on_dead_coordinator(self):
        srv = TCPStoreServer(0)
        try:
            wd1 = Watchdog(lambda t: TCPStore("127.0.0.1", srv.port,
                                       timeout_s=t),
                           1, 2, timeout_s=1.0, interval_s=0.1,
                           action="flag")
            with pytest.raises(PeerFailureError) as ei:
                wd1.monitored_barrier(timeout_s=1.0, tag="t2")
            assert ei.value.ranks == (0,)
        finally:
            srv.stop()

    def test_all_present_releases(self):
        srv = TCPStoreServer(0)
        try:
            wds = [Watchdog(lambda t: TCPStore("127.0.0.1", srv.port,
                                       timeout_s=t),
                            r, 2, timeout_s=5.0, interval_s=0.1,
                            action="flag") for r in range(2)]
            errs = []

            def go(wd):
                try:
                    wd.monitored_barrier(timeout_s=5.0, tag="t3")
                except Exception as e:
                    errs.append(e)
            ts = [threading.Thread(target=go, args=(wd,)) for wd in wds]
            [t.start() for t in ts]
            [t.join(timeout=10.0) for t in ts]
            assert not errs
            assert not any(t.is_alive() for t in ts)
        finally:
            srv.stop()


# =====================================================================
# Fault-injection harness
# =====================================================================
class TestFaultHarness:
    def test_registry_covers_every_knob(self):
        import inspect
        src = inspect.getsource(fault)
        for var in FI_ENV_VARS:
            assert var in src                     # every knob is wired
        # and fault.py reads no PADDLE_FI_* var that is NOT registered
        import re
        assert set(re.findall(r"PADDLE_FI_\w+", src)) == set(FI_ENV_VARS)

    def test_disarmed_is_free_noop(self):
        fault.reset()
        for _ in range(3):
            fault.inject("step")
        fault.inject("init")
        assert fault.step_count() == 0   # counter idle while disarmed

    def test_heartbeat_drop_predicate(self, monkeypatch):
        monkeypatch.setenv("PADDLE_FI_DROP_HEARTBEAT", "2")
        assert fault.heartbeat_dropped(2)
        assert not fault.heartbeat_dropped(0)

    def test_slow_injection_is_persistent_and_gated(self, monkeypatch):
        """The gray-failure flavor: PADDLE_FI_SLOW_MS slows EVERY
        occurrence of the target point from the AT_STEP-th onward
        (slowness is a condition, not a one-shot event) and leaves
        every other point untouched."""
        fault.reset()
        monkeypatch.setenv("PADDLE_FI_SLOW_MS", "40")
        monkeypatch.setenv("PADDLE_FI_SLOW_POINT", "serve_step")
        monkeypatch.setenv("PADDLE_FI_AT_STEP", "2")
        assert fault.slow_s("init") == 0.0        # wrong point: never
        # occurrences 0 and 1 are below the AT_STEP gate...
        assert fault.slow_s("serve_step") == 0.0
        assert fault.slow_s("serve_step") == 0.0
        # ...then EVERY occurrence is slowed (persistent, unlike KILL)
        assert fault.slow_s("serve_step") == pytest.approx(0.040)
        assert fault.slow_s("serve_step") == pytest.approx(0.040)
        fault.reset()

    def test_slow_injection_sleeps_in_inject(self, monkeypatch):
        fault.reset()
        monkeypatch.setenv("PADDLE_FI_SLOW_MS", "30")
        monkeypatch.setenv("PADDLE_FI_SLOW_POINT", "step")
        t0 = time.monotonic()
        fault.inject("step")
        assert time.monotonic() - t0 >= 0.025
        fault.reset()

    def test_rpc_flaky_schedule_is_deterministic(self, monkeypatch):
        """The flaky-transport error schedule is an accumulator, not a
        coin flip: exactly rate * calls errors after N calls, at the
        same call indices on every run (chaos drills must reproduce)."""
        fault.reset()
        monkeypatch.setenv("PADDLE_FI_RPC_ERR_RATE", "0.3")

        def run(n):
            idxs = []
            for i in range(n):
                try:
                    fault.rpc_flaky()
                except fault.FaultInjected:
                    idxs.append(i)
            return idxs

        first = run(20)
        assert len(first) == 6                    # floor(0.3 * 20)
        fault.reset()
        monkeypatch.setenv("PADDLE_FI_RPC_ERR_RATE", "0.3")
        assert run(20) == first                   # bit-for-bit replay
        fault.reset()

    def test_rpc_flaky_surfaces_as_replica_error(self, monkeypatch):
        """An injected transport error reaching RpcReplica._call must
        map to ReplicaError (the router's failover contract), exactly
        like a real timeout/connection failure."""
        from paddle_tpu.serving_cluster.replica import (ReplicaError,
                                                        RpcReplica)
        fault.reset()
        monkeypatch.setenv("PADDLE_FI_RPC_ERR_RATE", "1.0")
        rep = RpcReplica.__new__(RpcReplica)      # no live worker needed
        rep.name = "w0"
        rep._dead = False
        rep._timeout = 1.0
        from paddle_tpu.serving_cluster.replica import _HealthMeter
        rep._health = _HealthMeter()

        class _Stub:                              # the client-side hook
            def rpc_sync(self, name, fn, args=(), timeout=None):
                fault.rpc_flaky()                 # rides _call_inner
                return fn(*args)

        rep._rpc = _Stub()

        def _rw_submit():
            return "never reached"

        with pytest.raises(ReplicaError):
            rep._call(_rw_submit)
        assert rep._health.stats()["errors_total"] == 1
        fault.reset()

    def test_kill_at_step_exits_with_fi_code(self, tmp_path):
        code = ("from paddle_tpu.testing import fault\n"
                "for i in range(5):\n"
                "    fault.inject('step')\n"
                "raise SystemExit(0)\n")
        env = cpu_env(PADDLE_TRAINER_ID="0", PADDLE_FI_KILL_RANK="0",
                      PADDLE_FI_AT_STEP="2")
        r = run_child([sys.executable, "-c", code], env=env, cwd=REPO_ROOT,
                      timeout=120)
        assert r.returncode == fault.FI_EXIT_CODE, (r.stdout, r.stderr)
        assert "KILLED at step" in r.stdout

    def test_kill_at_init_point(self, tmp_path):
        code = ("from paddle_tpu.testing import fault\n"
                "fault.inject('init')\n"
                "raise SystemExit(0)\n")
        env = cpu_env(PADDLE_TRAINER_ID="3", PADDLE_FI_KILL_RANK="3")
        r = run_child([sys.executable, "-c", code], env=env, cwd=REPO_ROOT,
                      timeout=120)
        assert r.returncode == fault.FI_EXIT_CODE, (r.stdout, r.stderr)


# =====================================================================
# Auto-resume checkpoints: LATEST pointer, commit markers, pruning
# =====================================================================
class TestAutoResume:
    def _sd(self, val):
        return {"w": Tensor(np.full((4,), val, np.float32))}

    def test_latest_pointer_and_partial_dir_skipped(self, tmp_path):
        root = str(tmp_path / "ck")
        save_checkpoint(self._sd(1.0), root, 1)
        save_checkpoint(self._sd(2.0), root, 2)
        assert latest_step(root) == 2
        # a crash mid-write leaves a partial dir with NO commit marker
        os.makedirs(os.path.join(root, "step_3"))
        with open(os.path.join(root, "step_3", "junk"), "w") as f:
            f.write("partial")
        assert latest_step(root) == 2
        # even a corrupted LATEST pointing at the partial dir falls back
        # to the newest COMMITTED step via the scan
        with open(os.path.join(root, "LATEST"), "w") as f:
            f.write("step_3")
        assert latest_step(root) == 2
        dst = self._sd(0.0)
        assert load_latest(dst, root) == 2
        np.testing.assert_allclose(np.asarray(dst["w"]._data), 2.0)

    def test_no_checkpoint_returns_none(self, tmp_path):
        assert load_latest(self._sd(0.0), str(tmp_path / "missing")) is None
        assert latest_step(str(tmp_path / "missing")) is None

    def test_async_commit_gates_the_pointer(self, tmp_path):
        root = str(tmp_path / "ck")
        save_checkpoint(self._sd(1.0), root, 1)
        save_checkpoint(self._sd(5.0), root, 2, async_save=True)
        wait_all_async_saves()          # pointer lands with the commit
        assert latest_step(root) == 2
        dst = self._sd(0.0)
        assert load_latest(dst, root) == 2
        np.testing.assert_allclose(np.asarray(dst["w"]._data), 5.0)

    def test_corrupt_committed_payload_falls_back(self, tmp_path):
        """LATEST pointing at a committed dir whose payload is torn
        (power loss after the marker journaled but before the data
        pages) must fall back to the previous durable step — not fail
        every restart attempt."""
        root = str(tmp_path / "ck")
        save_checkpoint(self._sd(1.0), root, 1, local=True)
        save_checkpoint(self._sd(2.0), root, 2, local=True)
        with open(os.path.join(root, "step_2", "fallback.pdparams"),
                  "wb") as f:
            f.write(b"\x80\x04torn")        # truncated pickle
        dst = self._sd(0.0)
        assert load_latest(dst, root) == 1
        np.testing.assert_allclose(np.asarray(dst["w"]._data), 1.0)

    def test_local_async_commit_gates_the_pointer(self, tmp_path):
        """local=True honors async_save: the host snapshot is taken at
        call time (mutations after the call must not leak into the
        write) and the LATEST pointer lands at the join."""
        root = str(tmp_path / "ck")
        save_checkpoint(self._sd(1.0), root, 1, local=True)
        sd = self._sd(7.0)
        save_checkpoint(sd, root, 2, async_save=True, local=True)
        sd["w"].set_value(np.full((4,), -1.0, np.float32))
        wait_all_async_saves()          # pointer lands with the commit
        assert latest_step(root) == 2
        dst = self._sd(0.0)
        assert load_latest(dst, root) == 2
        np.testing.assert_allclose(np.asarray(dst["w"]._data), 7.0)

    def test_prune_keeps_newest_k(self, tmp_path):
        root = str(tmp_path / "ck")
        for s in range(1, 5):
            save_checkpoint(self._sd(float(s)), root, s, keep=2)
        assert latest_step(root) == 4
        names = sorted(d for d in os.listdir(root)
                       if d.startswith("step_"))
        assert names == ["step_3", "step_4"]


# =====================================================================
# RPC: bounded connect retry + env default per-call timeout
# =====================================================================
def _echo(x):
    return x


@needs_native
class TestRpcBounded:
    def _agent(self):
        from paddle_tpu.distributed import rpc
        return rpc, rpc.init_rpc("w0", rank=0, world_size=1,
                                 master_endpoint="127.0.0.1:0")

    def test_half_open_peer_times_out(self):
        rpc, agent = self._agent()
        silent = socket.socket()
        try:
            silent.bind(("127.0.0.1", 0))
            silent.listen(1)            # accepts, never answers
            agent.workers["dead"] = rpc.WorkerInfo(
                "dead", 1, "127.0.0.1", silent.getsockname()[1])
            t0 = time.monotonic()
            with pytest.raises(OSError):   # TimeoutError/socket.timeout
                rpc.rpc_sync("dead", _echo, args=(1,), timeout=1.0)
            assert time.monotonic() - t0 < 10.0
        finally:
            silent.close()
            rpc.shutdown()

    def test_slow_drip_peer_bounded_by_call_deadline(self):
        """A degraded peer dripping bytes keeps every per-op recv alive;
        only re-arming the timeout against the CALL deadline inside
        _recv_msg bounds the whole exchange."""
        rpc, agent = self._agent()
        drip = socket.socket()

        def _serve():
            conn, _ = drip.accept()
            with conn:
                conn.recv(1 << 16)                  # swallow the request
                import struct as _s
                conn.sendall(_s.pack("<Q", 64))     # promise 64 bytes...
                for _ in range(64):                 # ...drip them slowly
                    try:
                        conn.sendall(b"x")
                    except OSError:
                        return
                    time.sleep(0.5)

        try:
            drip.bind(("127.0.0.1", 0))
            drip.listen(1)
            threading.Thread(target=_serve, daemon=True).start()
            agent.workers["drip"] = rpc.WorkerInfo(
                "drip", 1, "127.0.0.1", drip.getsockname()[1])
            t0 = time.monotonic()
            with pytest.raises(OSError):   # TimeoutError/socket.timeout
                rpc.rpc_sync("drip", _echo, args=(1,), timeout=1.5)
            assert time.monotonic() - t0 < 10.0
        finally:
            drip.close()
            rpc.shutdown()

    def test_refused_connect_bounded_retry(self):
        rpc, agent = self._agent()
        try:
            with socket.socket() as s:   # grab a port nobody listens on
                s.bind(("127.0.0.1", 0))
                port = s.getsockname()[1]
            agent.workers["gone"] = rpc.WorkerInfo("gone", 1,
                                                   "127.0.0.1", port)
            t0 = time.monotonic()
            with pytest.raises(OSError):
                rpc.rpc_sync("gone", _echo, args=(1,), timeout=2.0)
            assert time.monotonic() - t0 < 10.0
        finally:
            rpc.shutdown()

    def test_env_default_timeout_applies(self, monkeypatch):
        monkeypatch.setenv("PADDLE_RPC_TIMEOUT_S", "1")
        rpc, agent = self._agent()
        silent = socket.socket()
        try:
            silent.bind(("127.0.0.1", 0))
            silent.listen(1)
            agent.workers["dead"] = rpc.WorkerInfo(
                "dead", 1, "127.0.0.1", silent.getsockname()[1])
            t0 = time.monotonic()
            with pytest.raises(OSError):
                rpc.rpc_sync("dead", _echo, args=(1,))  # timeout=None
            assert time.monotonic() - t0 < 8.0
        finally:
            silent.close()
            rpc.shutdown()

    def test_self_roundtrip_still_works(self):
        rpc, _ = self._agent()
        try:
            assert rpc.rpc_sync("w0", _echo, args=(42,)) == 42
        finally:
            rpc.shutdown()


# =====================================================================
# Watchdog escalation: a wedged main thread is hard-exited after grace
# =====================================================================
WEDGED = """
import os, threading
os.environ["PADDLE_TRAINER_ID"] = "0"
from paddle_tpu.core.native import TCPStore, TCPStoreServer
from paddle_tpu.distributed.resilience import Watchdog
srv = TCPStoreServer(0)
wd = Watchdog(lambda t: TCPStore("127.0.0.1", srv.port,
                                       timeout_s=t), 0, 2,
              timeout_s=1.0, interval_s=0.2, action="raise",
              kill_grace_s=1.0).start()
threading.Event().wait(60)   # "hung collective": no bytecode runs, the
                             # async-raise can never land -> escalation
raise SystemExit(0)
"""


@needs_native
class TestWatchdogEscalation:
    def test_wedged_rank_hard_exits_with_watchdog_code(self, tmp_path):
        script = tmp_path / "wedged.py"
        script.write_text(WEDGED)
        t0 = time.monotonic()
        r = run_child([sys.executable, str(script)], env=cpu_env(),
                      cwd=REPO_ROOT, timeout=120)
        assert r.returncode == WATCHDOG_EXIT_CODE, (r.stdout, r.stderr)
        assert "no heartbeat from rank 1" in r.stdout
        assert time.monotonic() - t0 < 60.0


# =====================================================================
# Gang supervisor (launcher) behavior
# =====================================================================
SLOW_SURVIVOR = """
import os, sys, time
if os.environ["PADDLE_TRAINER_ID"] == "1":
    print("rank 1 failing now", flush=True)
    sys.exit(7)
time.sleep(120)
"""

GEN_LOGGER = """
import os, sys
gen = int(os.environ["PADDLE_RESTART_COUNT"])
print("generation", gen, "rank", os.environ["PADDLE_TRAINER_ID"],
      flush=True)
sys.exit(0 if gen > 0 else 1)
"""


class TestGangSupervisor:
    def test_survivors_reaped_promptly_with_report(self, tmp_path):
        """Old launcher: serial wait() sat out rank 0's full 120 s sleep.
        Supervisor: first bad exit tears the gang down in seconds and
        prints an attributable per-rank report with the log tail."""
        t0 = time.monotonic()
        r = run_launch(tmp_path, SLOW_SURVIVOR,
                        ["--nproc_per_node", "2"], [], timeout=90)
        assert r.returncode == 7, (r.stdout, r.stderr)
        assert time.monotonic() - t0 < 60.0
        assert "failure report" in r.stderr
        assert "rank 1: exit 7" in r.stderr
        assert "rank 1 failing now" in r.stderr     # workerlog tail

    def test_workerlog_rotates_per_generation(self, tmp_path):
        r = run_launch(tmp_path, GEN_LOGGER,
                        ["--nproc_per_node", "2", "--max_restart", "1",
                         "--restart_backoff", "0.1"], [])
        assert r.returncode == 0, (r.stdout, r.stderr)
        log = tmp_path / "log"
        assert "generation 0" in (log / "workerlog.1").read_text()
        assert "generation 1" in (
            log / "workerlog.1.restart1").read_text()
        assert "PADDLE_RESTART_COUNT=1" in r.stderr


# =====================================================================
# End-to-end: hang -> watchdog PeerFailureError -> supervisor restart
# -> auto-resume -> completion  (the acceptance loop, all on CPU/gloo)
# =====================================================================
FT_E2E = """
import os, sys, time
gen = int(os.environ.get("PADDLE_RESTART_COUNT", "0"))
workdir = sys.argv[1]
rank_s = os.environ["PADDLE_TRAINER_ID"]
open(f"{workdir}/gen.{gen}.{rank_s}", "w").write("1")
if gen == 0:
    # generation 0: rank 1 goes dark mid-run — heartbeat publisher
    # silenced AND the rank wedges at train step 2 (hang, not crash: the
    # harder failure mode, invisible to the supervisor's exit polling).
    # DROP_HEARTBEAT is armed inside the loop AT the wedge step, not
    # here: the publisher consults the env before every beat, so arming
    # it now would silence rank 1 from t=0 — and when jit compilation
    # pushes the first steps past the watchdog window, rank 0 would
    # detect the "dead" peer before committing a single checkpoint,
    # leaving generation 1 nothing to resume from.
    os.environ["PADDLE_FI_HANG"] = "1"
    os.environ["PADDLE_FI_AT_STEP"] = "2"
os.environ["PADDLE_WATCHDOG_TIMEOUT_S"] = "2"
os.environ["PADDLE_HEARTBEAT_INTERVAL_S"] = "0.2"
os.environ["PADDLE_WATCHDOG_ACTION"] = "flag"   # surface via the step hook
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.resilience import PeerFailureError

env = dist.init_parallel_env()
rank, world = env.rank, env.world_size
assert world == 2, world

steps = 12
paddle.seed(11)
m = paddle.nn.Linear(4, 1)
opt = paddle.optimizer.SGD(0.1, parameters=m.parameters())
sd = {"w": m.parameters()[0], "b": m.parameters()[1]}
root = os.path.join(workdir, "ckpt")

start = 0
resumed = dist.load_latest(sd, root)     # both ranks read the shared dir
if resumed is not None:
    start = resumed
    open(f"{workdir}/resumed_from.{gen}.{rank_s}", "w").write(str(resumed))
# no rank saves before both have looked: on a loaded host rank 1 came here
# seconds after rank 0, resumed generation 0 from rank 0's step 5, never
# reached the loop's step 2 that silences its heartbeat, and hung with it
# beating; rank 0 finished its steps and sat out jax's shutdown barrier
dist.barrier()

rng = np.random.RandomState(0)
xs = rng.randn(steps, 8, 4).astype(np.float32)
w_true = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)

try:
    for step in range(start, steps):
        if gen == 0 and rank == 1 and step == 2:
            os.environ["PADDLE_FI_DROP_HEARTBEAT"] = "1"
        x = paddle.to_tensor(xs[step])
        y = paddle.to_tensor(xs[step] @ w_true)
        loss = ((m(x) - y) ** 2).mean()
        loss.backward()
        opt.step()         # fault-injection + watchdog hooks live here
        opt.clear_grad()
        if rank == 0:
            # local=True: rank-0-only checkpoint of replicated state —
            # no Orbax cross-process sync (which a half-dead gang could
            # never complete)
            dist.save_checkpoint(sd, root, step + 1, local=True)
        time.sleep(0.4)    # outlast the watchdog window (bounded)
except PeerFailureError as e:
    open(f"{workdir}/peer_failure.{rank_s}.{gen}", "w").write(str(e))
    # os._exit, NOT sys.exit: jax's atexit shutdown waits on the DEAD
    # peer (exactly the hang the watchdog exists to break). The library
    # backstop for this is action="raise"'s hard-exit escalation; a
    # supervised train loop that catches PeerFailureError itself exits
    # hard after recording, like every production elastic agent.
    os._exit(31)

open(f"{workdir}/done.{rank_s}", "w").write(str(steps))
print("rank", rank_s, "gen", gen, "completed", steps, "steps")
"""


@needs_native
class TestFaultToleranceEndToEnd:
    def test_hang_detect_restart_resume_completes(self, tmp_path):
        """The full loop from the acceptance criteria: a rank wedges
        mid-run (dropped heartbeat + hang, no exit for the supervisor to
        see) -> the SURVIVING rank raises PeerFailureError via the
        watchdog within the configured timeout and exits -> the gang
        supervisor tears down the wedged rank, restarts with backoff and
        a bumped PADDLE_RESTART_COUNT -> generation 1 resumes from
        load_latest() and completes. Entire test bounded by the
        subprocess timeout."""
        r = run_launch(tmp_path, FT_E2E,
                        ["--nproc_per_node", "2", "--max_restart", "2",
                         "--restart_backoff", "0.2"],
                        [str(tmp_path)])
        assert r.returncode == 0, (r.stdout, r.stderr)
        # generation 0 ran both ranks; generation 1 proves the restart
        # and the PADDLE_RESTART_COUNT env contract
        for marker in ("gen.0.0", "gen.0.1", "gen.1.0", "gen.1.1"):
            assert (tmp_path / marker).exists(), (marker, r.stderr)
        # the SURVIVOR (rank 0) raised PeerFailureError naming rank 1 —
        # detection, not a hang
        pf = tmp_path / "peer_failure.0.0"
        assert pf.exists(), (r.stdout, r.stderr)
        assert "no heartbeat from rank 1" in pf.read_text()
        # supervisor: report + backoff restart in stderr
        assert "failure report" in r.stderr
        assert "restarting" in r.stderr
        # generation 1 RESUMED from a durable step (not step 0) ...
        resumed = tmp_path / "resumed_from.1.0"
        assert resumed.exists()
        assert int(resumed.read_text()) >= 1
        # ... and the job completed on both ranks
        assert (tmp_path / "done.0").read_text() == "12"
        assert (tmp_path / "done.1").read_text() == "12"


FI_KILL = """
import os, sys
gen = int(os.environ.get("PADDLE_RESTART_COUNT", "0"))
workdir = sys.argv[1]
if gen == 0:
    os.environ["PADDLE_FI_KILL_RANK"] = "0"
    os.environ["PADDLE_FI_AT_STEP"] = "1"
os.environ["PADDLE_WATCHDOG_TIMEOUT_S"] = "0"   # isolate the kill path
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

m = paddle.nn.Linear(2, 1)
opt = paddle.optimizer.SGD(0.1, parameters=m.parameters())
sd = {"w": m.parameters()[0]}
root = os.path.join(workdir, "ckpt")
start = dist.load_latest(sd, root) or 0
for step in range(start, 4):
    x = paddle.to_tensor(np.ones((2, 2), np.float32))
    loss = (m(x) ** 2).mean()
    loss.backward()
    opt.step()               # PADDLE_FI_KILL_RANK fires here at step 1
    opt.clear_grad()
    dist.save_checkpoint(sd, root, step + 1)
open(f"{workdir}/done.{gen}", "w").write(str(start))
"""


class TestFaultInjectionKillResume:
    def test_kill_restart_resumes_from_latest(self, tmp_path):
        r = run_launch(tmp_path, FI_KILL,
                        ["--nproc_per_node", "1", "--max_restart", "1",
                         "--restart_backoff", "0.1"],
                        [str(tmp_path)])
        assert r.returncode == 0, (r.stdout, r.stderr)
        assert f"exit {fault.FI_EXIT_CODE}" in r.stderr  # attributed
        done = tmp_path / "done.1"
        assert done.exists()
        assert int(done.read_text()) >= 1     # generation 1 RESUMED
