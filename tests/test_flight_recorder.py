"""Distributed flight recorder (ISSUE 9): per-rank collective event
rings, hang dumps, cross-rank desync diagnosis, cluster aggregation.

Contracts under test:
  * ring bounding + strict seq monotonicity; an in-flight (hung) event
    survives ring eviction;
  * disabled mode (`ring=0`): zero collection AND zero clock reads
    (counting-clock bound, same discipline as telemetry-off);
  * the choke point: every public collective records exactly ONE event
    (nested object-collectives suppressed), payload introspection,
    tracer-backed calls skipped, per-op wait histograms land in the
    runtime registry;
  * dump format: self-describing header (generation, watchdog gauges),
    all-thread stacks with the main thread tagged, faulthandler text,
    runtime registry snapshot; dump-once semantics;
  * cross-rank diagnosis: never-entered stragglers, the async
    in-flight-behind pattern, all-ranks-wedged, missing/unparsable
    dumps NAMED; deterministic text (byte-for-byte reproducible);
  * gang supervisor emission: `gang_diagnosis` logjson event with the
    structured verdict;
  * TCPStore cluster snapshot aggregation (heartbeat-style keys);
  * pid-per-rank Perfetto export over profiler.ChromeTrace;
  * structural checks (tools/check_collective_surface.py) pass tier-1;
  * END TO END on the gloo path: PADDLE_FI_HANG wedges one rank at a
    collective; the supervisor report names the stuck op + seq + the
    straggler rank; dumps contain in-collective stacks; and
    tools/flight_report.py reproduces the supervisor's diagnosis
    byte-for-byte. Every wait is bounded.
"""
import importlib.util
import io
import json
import os
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.core.native import TCPStore, TCPStoreServer, load_native
from paddle_tpu.distributed.resilience import flight_recorder as fr
from paddle_tpu.testing import FI_ENV_VARS, FR_ENV_VARS, fault
from paddle_tpu.testing.child import REPO_ROOT, run_launch

needs_native = pytest.mark.skipif(load_native() is None,
                                  reason="native runtime unavailable")


@pytest.fixture
def rec():
    """A module-global recorder for choke-point tests; always reset so
    the cached global never leaks into other suites."""
    r = fr.configure(ring=64, rank=0, world=1)
    yield r
    fr.reset()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO_ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# =====================================================================
# Recorder core
# =====================================================================
class TestRecorderCore:
    def test_seq_monotonic_and_ring_bounded(self):
        r = fr.FlightRecorder(ring=8, rank=0, world=1)
        for i in range(20):
            r.end(r.start("all_reduce", group="default", shape=(4,),
                          dtype="float32", nbytes=16))
        tail = r.tail()
        assert len(tail) == 8                       # bounded
        seqs = [e["seq"] for e in tail]
        assert seqs == sorted(seqs) == list(range(13, 21))
        assert all(e["status"] == "done" for e in tail)
        assert r.snapshot()["events_recorded"] == 20

    def test_gseq_is_per_group(self):
        r = fr.FlightRecorder(ring=16, rank=0, world=1)
        r.end(r.start("all_reduce", group="mp"))
        r.end(r.start("all_reduce", group="pp"))
        r.end(r.start("broadcast", group="mp"))
        by = {(e["group"], e["op"]): e["gseq"] for e in r.tail()}
        assert by[("mp", "all_reduce")] == 1
        assert by[("pp", "all_reduce")] == 1        # independent counter
        assert by[("mp", "broadcast")] == 2

    def test_in_flight_event_survives_ring_eviction(self):
        """THE hang case: the wedged collective must stay visible in
        tail() even after chatty later events (rpc from other threads)
        rotated it out of the ring."""
        r = fr.FlightRecorder(ring=4, rank=0, world=1)
        hung = r.start("all_reduce", group="mp", shape=(8,),
                       dtype="float32")
        for _ in range(10):
            r.end(r.start("rpc", kind="rpc", group="rpc:w1"))
        tail = r.tail()
        assert len(tail) == 5                       # ring + the hung one
        assert tail[0] is not hung                  # copies, not refs
        assert tail[0]["seq"] == hung["seq"]
        assert tail[0]["status"] == "in_flight"
        r.end(hung)
        assert all(e["status"] == "done" for e in r.tail())

    def test_disabled_zero_collection_zero_clock_reads(self):
        calls = [0]

        def counting_clock():
            calls[0] += 1
            return time.monotonic()

        r = fr.FlightRecorder(ring=0, rank=0, world=1,
                              clock=counting_clock)
        assert not r.enabled
        for _ in range(50):
            r.end(r.start("all_reduce", group="default"))
        assert calls[0] == 0                        # no clock reads at all
        assert r.tail() == []
        assert r.snapshot()["events_recorded"] == 0
        with pytest.raises(ValueError, match=">= 0"):
            fr.FlightRecorder(ring=-1)

    def test_error_status_and_wait_histogram(self):
        r = fr.FlightRecorder(ring=8, rank=0, world=1)
        ev = r.start("reduce_scatter", group="default")
        r.end(ev, error=RuntimeError("boom"))
        (e,) = r.tail()
        assert e["status"] == "error" and "boom" in e["error"]
        from paddle_tpu.inference.telemetry import (
            runtime_prometheus, runtime_registry_snapshot)
        name = fr.runtime_hist_name("reduce_scatter")
        snap = runtime_registry_snapshot()
        assert name in snap["histograms"]
        assert snap["histograms"][name]["count"] >= 1
        assert f"{name}_bucket" in "\n".join(runtime_prometheus())

    def test_env_default_on_iff_multiprocess(self, monkeypatch):
        monkeypatch.delenv("PADDLE_FLIGHT_RECORDER", raising=False)
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
        fr.reset()
        assert fr.recorder() is None                # single-process: off
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
        fr.reset()
        r = fr.recorder()
        assert r is not None and r.ring == fr.DEFAULT_RING
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", "0")
        fr.reset()
        assert fr.recorder() is None                # explicit off wins
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", "32")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
        fr.reset()
        assert fr.recorder().ring == 32             # explicit on wins
        fr.reset()

    def test_malformed_env_degrades_to_default_policy(self, monkeypatch):
        """recorder() is called lazily from inside the first collective
        — a typo'd env var must warn and fall back, not kill the job
        with a traceback pointing into an all_reduce."""
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", "true")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
        fr.reset()
        r = fr.recorder()
        assert r is not None and r.ring == fr.DEFAULT_RING
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", "-5")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
        fr.reset()
        assert fr.recorder() is None                # default: off at w=1
        fr.reset()

    def test_configure_world_hint_enables_without_env(self, monkeypatch):
        """A jax-native launch never sets PADDLE_TRAINERS_NUM — the
        authoritative world passed by init_parallel_env must drive the
        default-on decision."""
        monkeypatch.delenv("PADDLE_FLIGHT_RECORDER", raising=False)
        monkeypatch.delenv("PADDLE_TRAINERS_NUM", raising=False)
        monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
        rec = fr.configure(rank=2, world=4)
        assert rec is not None and rec.enabled
        assert rec.rank == 2 and rec.world == 4
        fr.reset()
        assert fr.configure(rank=0, world=1) is None
        monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
        fr.reset()
        assert fr.recorder() is not None            # env contract too
        fr.reset()


# =====================================================================
# The choke point (instrumented public collectives)
# =====================================================================
class TestChokePoint:
    def test_public_collectives_record_one_event_each(self, rec):
        t = paddle.to_tensor(np.ones((4,), np.float32))
        dist.all_reduce(t)
        dist.barrier()
        objs = []
        dist.all_gather_object(objs, {"x": 1})      # nests 2 all_gathers
        ops = [e["op"] for e in rec.tail()]
        assert ops == ["all_reduce", "barrier", "all_gather_object"]
        ev = rec.tail()[0]
        assert ev["shape"] == [4] and ev["dtype"] == "float32"
        assert ev["nbytes"] == 16
        assert ev["group"] == "default"
        assert [e["gseq"] for e in rec.tail()] == [1, 2, 3]

    def test_named_group_events_align_on_group_name(self, rec):
        g = dist.new_group([0])
        t = paddle.to_tensor(np.zeros((2,), np.float32))
        dist.all_reduce(t, group=g)
        (ev,) = [e for e in rec.tail() if e["op"] == "all_reduce"]
        assert ev["group"] == g.name

    def test_disabled_recorder_skips_everything(self, monkeypatch):
        monkeypatch.delenv("PADDLE_FLIGHT_RECORDER", raising=False)
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
        fr.reset()
        assert fr.recorder() is None
        t = paddle.to_tensor(np.ones((4,), np.float32))
        dist.all_reduce(t)                          # must not blow up
        np.testing.assert_allclose(np.asarray(t._data), 1.0)
        fr.reset()

    def test_tracer_backed_payload_is_skipped(self, rec):
        tracer_like = types.SimpleNamespace(_trace=None, shape=(2,),
                                            dtype=np.dtype(np.float32))
        assert fr._payload_of((tracer_like,), {}) is fr._SKIP
        called = []

        @fr.instrumented("fake_op")
        def fake(x):
            called.append(x)
            return x

        fake(types.SimpleNamespace(_data=tracer_like))
        # keyword form must hit the same guard (traced calls record
        # per-compile, not per-execution — they must be skipped)
        assert fr._payload_of(
            (), {"tensor": types.SimpleNamespace(_data=tracer_like)}) \
            is fr._SKIP
        fake(x=types.SimpleNamespace(_data=tracer_like))
        assert len(called) == 2                     # ran untouched
        assert all(e["op"] != "fake_op" for e in rec.tail())

    def test_record_span_is_reentrancy_safe(self, rec):
        with fr.record_span("outer", group="g"):
            with fr.record_span("inner", group="g"):
                pass
        ops = [e["op"] for e in rec.tail()]
        assert ops == ["outer"]                     # outermost only

    def test_rpc_call_records_span(self, rec):
        if load_native() is None:
            pytest.skip("native runtime unavailable")
        from paddle_tpu.distributed import rpc
        rpc.init_rpc("w0", rank=0, world_size=1,
                     master_endpoint="127.0.0.1:0")
        try:
            assert rpc.rpc_sync("w0", _echo, args=(7,)) == 7
        finally:
            rpc.shutdown()
        evs = [e for e in rec.tail() if e["kind"] == "rpc"]
        assert evs and evs[-1]["op"] == "rpc"
        assert evs[-1]["group"] == "rpc:w0"
        assert evs[-1]["note"] == "_echo"
        assert evs[-1]["status"] == "done"

    def test_monitored_barrier_records_span(self, rec):
        if load_native() is None:
            pytest.skip("native runtime unavailable")
        from paddle_tpu.distributed.resilience import Watchdog
        srv = TCPStoreServer(0)
        try:
            wd = Watchdog(lambda t: TCPStore("127.0.0.1", srv.port,
                                             timeout_s=t),
                          0, 2, timeout_s=1.0, interval_s=0.1,
                          action="flag")
            from paddle_tpu.distributed.resilience import PeerFailureError
            with pytest.raises(PeerFailureError):
                wd.monitored_barrier(timeout_s=0.5, tag="fr-t")
        finally:
            srv.stop()
        evs = [e for e in rec.tail() if e["op"] == "monitored_barrier"]
        assert evs and evs[0]["status"] == "error"
        assert evs[0]["group"] == "world"

    def test_structural_check_passes(self, capsys):
        """tools/check_collective_surface.py: no public collective
        bypasses the choke point — tier-1, like the metrics surface."""
        mod = _load_tool("check_collective_surface")
        rc = mod.main()
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "ok" in out


def _echo(x):
    return x


# =====================================================================
# Fault-injection point targeting (PADDLE_FI_AT_POINT)
# =====================================================================
class TestFaultAtPoint:
    def test_registry_covers_new_knob(self):
        assert "PADDLE_FI_AT_POINT" in FI_ENV_VARS
        assert FR_ENV_VARS == ("PADDLE_FLIGHT_DUMP_DIR",
                               "PADDLE_FLIGHT_RECORDER")

    def test_at_point_gates_named_point(self, monkeypatch):
        fault.reset()
        monkeypatch.setenv("PADDLE_FI_HANG", "0")
        monkeypatch.setenv("PADDLE_FI_AT_POINT", "collective")
        monkeypatch.delenv("PADDLE_FI_AT_STEP", raising=False)
        assert not fault._should_fire("init")       # init no longer fires
        assert not fault._should_fire("step")
        assert fault._should_fire("collective")     # first occurrence
        fault.reset()

    def test_at_point_with_index(self, monkeypatch):
        fault.reset()
        monkeypatch.setenv("PADDLE_FI_HANG", "0")
        monkeypatch.setenv("PADDLE_FI_AT_POINT", "collective")
        monkeypatch.setenv("PADDLE_FI_AT_STEP", "2")
        fires = [fault._should_fire("collective") for _ in range(4)]
        assert fires == [False, False, True, False]  # exactly the 3rd
        fault.reset()

    def test_legacy_semantics_unchanged(self, monkeypatch):
        fault.reset()
        monkeypatch.setenv("PADDLE_FI_KILL_RANK", "0")
        monkeypatch.delenv("PADDLE_FI_AT_POINT", raising=False)
        monkeypatch.setenv("PADDLE_FI_AT_STEP", "1")
        assert not fault._should_fire("init")       # gated to a step
        assert not fault._should_fire("collective")
        assert not fault._should_fire("step")       # step 0
        assert fault._should_fire("step")           # step 1
        monkeypatch.delenv("PADDLE_FI_AT_STEP", raising=False)
        assert fault._should_fire("init")           # legacy default
        fault.reset()


# =====================================================================
# Dumps
# =====================================================================
class TestDump:
    def test_dump_is_self_describing(self, tmp_path, monkeypatch, rec):
        monkeypatch.setenv("PADDLE_RESTART_COUNT", "3")
        t = paddle.to_tensor(np.ones((4,), np.float32))
        dist.all_reduce(t)
        hung = rec.start("broadcast", group="mp", shape=(2, 2),
                         dtype="float32", nbytes=16)
        path = rec.dump(path=str(tmp_path / "flightdump.0.3.json"),
                        reason="unit")
        with open(path) as f:
            d = json.load(f)
        assert d["schema"] == fr.DUMP_SCHEMA
        assert d["rank"] == 0 and d["generation"] == 3
        assert d["reason"] == "unit" and d["pid"] == os.getpid()
        assert d["t_mono"] > 0 and d["t_wall"] > 0
        ops = {e["op"]: e["status"] for e in d["events"]}
        assert ops["all_reduce"] == "done"
        assert ops["broadcast"] == "in_flight"
        # all-thread stacks, main thread tagged, this test in the frames
        main = [k for k in d["stacks"] if k.endswith("[main]")]
        assert len(main) == 1
        frames = d["stacks"][main[0]]
        assert any("test_flight_recorder" in fs["file"] for fs in frames)
        assert "Thread" in d["faulthandler"] or \
            "thread" in d["faulthandler"]
        assert "histograms" in d["runtime_metrics"]
        rec.end(hung)

    @needs_native
    def test_watchdog_gauges_in_dump_header(self, rec):
        """Satellite: heartbeat ages + restart generation make a dump
        self-describing without the supervisor's context."""
        from paddle_tpu.distributed.resilience import watchdog as wdmod
        srv = TCPStoreServer(0)
        wd = wdmod.Watchdog(lambda t: TCPStore("127.0.0.1", srv.port,
                                               timeout_s=t),
                            0, 2, timeout_s=30.0, interval_s=0.1,
                            action="flag").start()
        wdmod._watchdog[0] = wd
        try:
            time.sleep(0.3)
            d = rec.dump_payload(reason="unit")
            assert d["watchdog"] is not None
            g = d["watchdog"]["gauges"]
            assert g["rank"] == 0 and g["world"] == 2
            assert 1 in g["heartbeat_age_s"] or \
                "1" in g["heartbeat_age_s"]
            assert d["watchdog"]["failure"] is None
        finally:
            wdmod._watchdog[0] = None
            wd.stop()
            srv.stop()

    def test_dump_once_keeps_first_failure_view(self, tmp_path, rec):
        p1 = rec.dump(path=str(tmp_path / "flightdump.0.0.json"),
                      reason="peer_failure")
        rec.end(rec.start("all_reduce"))
        p2 = rec.dump(path=str(tmp_path / "other.json"),
                      reason="sigterm")            # cascading trigger
        assert p1 == p2                            # first view wins
        with open(p1) as f:
            assert json.load(f)["reason"] == "peer_failure"
        assert not (tmp_path / "other.json").exists()
        p3 = rec.dump(path=str(tmp_path / "forced.json"),
                      reason="manual", force=True)
        assert p3.endswith("forced.json")

    def test_module_dump_on_failure_best_effort(self, tmp_path,
                                                monkeypatch, rec):
        monkeypatch.setenv("PADDLE_FLIGHT_DUMP_DIR", str(tmp_path))
        monkeypatch.setenv("PADDLE_RESTART_COUNT", "0")
        rec.end(rec.start("all_reduce"))
        path = fr.dump_on_failure("peer_failure")
        assert path == str(tmp_path / "flightdump.0.0.json")
        assert os.path.exists(path)


# =====================================================================
# Cross-rank diagnosis (synthesized dumps — documents the schema)
# =====================================================================
def _ev(seq, op, group="default", status="done", t0=10.0, t1=10.5,
        kind="collective"):
    return {"seq": seq, "gseq": seq, "op": op, "group": group,
            "kind": kind, "status": status, "t_start": t0,
            "t_end": None if status == "in_flight" else t1}


def _dump(rank, events, world=2, t_mono=50.0, stacks=None, wd=None,
          generation=0):
    return {"schema": fr.DUMP_SCHEMA, "rank": rank, "world": world,
            "generation": generation, "pid": 1000 + rank,
            "reason": "unit", "t_wall": 1e9 + t_mono, "t_mono": t_mono,
            "ring": 64, "events_recorded": len(events),
            "events": events, "watchdog": wd,
            "stacks": stacks or {}, "faulthandler": "",
            "runtime_metrics": None}


class TestDiagnosis:
    def test_never_entered_straggler_named(self):
        dumps = {
            0: _dump(0, [_ev(3, "all_reduce"),
                         _ev(4, "all_reduce", status="in_flight",
                             t0=12.0)]),
            1: _dump(1, [_ev(3, "all_reduce")],
                     stacks={"MainThread (tid 7) [main]": [
                         {"file": "/x/train.py", "line": 9,
                          "func": "<module>",
                          "code": "dist.all_reduce(t)"}]}),
        }
        text, diag = fr.diagnose(dumps, world=2, generation=0)
        assert diag["desync"] and diag["stragglers"] == [1]
        assert diag["stuck"] == {"group": "default", "op": "all_reduce",
                                 "seq": 4}
        assert "rank 0: in_flight in all_reduce seq=4 for 38.00s" in text
        assert "rank 1: completed seq=3, never entered all_reduce " \
            "seq=4" in text
        assert "stragglers: rank 1" in text
        assert "straggler rank 1 main-thread stack" in text
        assert "train.py:9 <module>: dist.all_reduce(t)" in text

    def test_in_flight_behind_pattern(self):
        """The NCCL-async exemplar: rank 2 still inside seq 417 while
        ranks 0,1,3 moved on to seq 418."""
        behind = [_ev(417, "all_reduce", group="mp",
                      status="in_flight", t0=12.0)]
        ahead = [_ev(417, "all_reduce", group="mp"),
                 _ev(418, "all_reduce", group="mp",
                     status="in_flight", t0=49.0)]
        dumps = {0: _dump(0, list(ahead), world=4),
                 1: _dump(1, list(ahead), world=4),
                 2: _dump(2, behind, world=4),
                 3: _dump(3, list(ahead), world=4)}
        text, diag = fr.diagnose(dumps, world=4, generation=2)
        assert diag["stragglers"] == [2]
        assert diag["stuck"] == {"group": "mp", "op": "all_reduce",
                                 "seq": 417}
        assert "rank 2: in_flight in all_reduce seq=417 for 38.00s" \
            in text
        assert "(waiting on stragglers)" in text    # ranks 0,1,3

    def test_wedged_inside_collective_peers_left(self):
        """Async completion: every peer finished seq 4 and LEFT the
        collective; the one rank still inside it IS the straggler (not
        'none identified')."""
        dumps = {
            0: _dump(0, [_ev(4, "all_reduce")]),
            1: _dump(1, [_ev(4, "all_reduce", status="in_flight",
                             t0=12.0)]),
        }
        text, diag = fr.diagnose(dumps, world=2)
        assert diag["desync"] and diag["stragglers"] == [1]
        assert "rank 1: in_flight in all_reduce seq=4 for 38.00s" \
            in text
        assert "(waiting on stragglers)" not in text  # it IS the straggler
        assert "stragglers: rank 1" in text
        assert "none identified" not in text

    def test_never_entered_names_the_stuck_seq_when_far_behind(self):
        """A straggler 3 collectives behind must be pointed at the seq
        the peers are actually stuck in, not last+1."""
        dumps = {
            0: _dump(0, [_ev(5, "all_reduce", status="in_flight",
                             t0=12.0)]),
            1: _dump(1, [_ev(2, "all_reduce")]),
        }
        text, diag = fr.diagnose(dumps, world=2)
        assert diag["stragglers"] == [1]
        assert "rank 1: completed seq=2, never entered all_reduce " \
            "seq=5" in text

    def test_all_ranks_wedged_has_no_scapegoat(self):
        evs = [_ev(4, "all_reduce", status="in_flight", t0=12.0)]
        dumps = {r: _dump(r, list(evs)) for r in range(2)}
        text, diag = fr.diagnose(dumps, world=2)
        assert diag["desync"] and diag["stragglers"] == []
        assert "collective itself is wedged" in text

    def test_missing_and_unparsable_dumps_named(self, tmp_path):
        """Satellite: a rank that crashed before dumping must be NAMED,
        not silently omitted."""
        with open(tmp_path / "flightdump.0.0.json", "w") as f:
            json.dump(_dump(0, [_ev(1, "all_reduce",
                                    status="in_flight", t0=12.0)],
                            world=3), f)
        with open(tmp_path / "flightdump.1.0.json", "w") as f:
            f.write("{torn json")
        text, diag = fr.diagnose_dir(str(tmp_path), world=3)
        assert diag["ranks_with_dump"] == [0]
        assert diag["ranks_missing_dump"] == [1, 2]
        assert "unparsable" in diag["missing_dump_errors"]["1"]
        assert "rank 2 (no dump file" in text
        assert "rank 1 (unparsable" in text
        # missing-dump ranks are straggler suspects: they never entered
        assert 1 in diag["stragglers"] and 2 in diag["stragglers"]

    def test_expected_ranks_bounds_missing_dump_suspects(self):
        """Multi-node: a node-0 supervisor only sees ranks 0-1's dumps;
        ranks 2-3 dump on their own host and must NOT be reported as
        crashed-before-dumping stragglers."""
        dumps = {0: _dump(0, [_ev(2, "all_reduce", status="in_flight",
                                  t0=12.0)], world=4),
                 1: _dump(1, [_ev(1, "all_reduce")], world=4)}
        text, diag = fr.diagnose(dumps, world=4, expected_ranks=[0, 1])
        assert diag["ranks_missing_dump"] == []
        assert diag["stragglers"] == [1]
        assert "missing dumps" not in text
        # default (single-node): every rank in world is expected
        _, diag_all = fr.diagnose(dumps, world=4)
        assert diag_all["ranks_missing_dump"] == [2, 3]

    def test_aligned_gang_reports_no_desync(self):
        evs = [_ev(5, "all_reduce"), _ev(6, "barrier")]
        dumps = {r: _dump(r, [dict(e) for e in evs]) for r in range(2)}
        text, diag = fr.diagnose(dumps, world=2)
        assert not diag["desync"] and diag["stragglers"] == []
        assert "no cross-rank desync detected" in text
        assert "group 'default': aligned at seq 6" in text

    def test_watchdog_flags_and_rpc_in_flight_surface(self):
        wd = {"gauges": {"rank": 0}, "failure": "no heartbeat",
              "failure_ranks": [1]}
        dumps = {0: _dump(0, [_ev(2, "all_reduce", status="in_flight",
                                  t0=12.0),
                              _ev(3, "rpc", group="rpc:w1",
                                  kind="rpc", status="in_flight",
                                  t0=20.0)], wd=wd),
                 1: _dump(1, [_ev(1, "all_reduce")])}
        text, diag = fr.diagnose(dumps, world=2)
        assert "watchdog flags: rank 0 -> [1]" in text
        assert "rank 0: rpc in_flight in rpc group=rpc:w1 for 30.00s" \
            in text

    def test_text_is_deterministic(self, tmp_path):
        for r in range(2):
            with open(tmp_path / f"flightdump.{r}.0.json", "w") as f:
                json.dump(_dump(r, [_ev(1, "all_reduce",
                                        status="in_flight", t0=1.0)]),
                          f)
        t1, _ = fr.diagnose_dir(str(tmp_path))
        t2, _ = fr.diagnose_dir(str(tmp_path))
        assert t1 == t2

    def test_generation_selection(self, tmp_path):
        for gen, seq in ((0, 1), (1, 9)):
            with open(tmp_path / f"flightdump.0.{gen}.json", "w") as f:
                json.dump(_dump(0, [_ev(seq, "all_reduce")], world=1,
                                generation=gen), f)
        gen, dumps, _ = fr.load_dumps(str(tmp_path))
        assert gen == 1                             # newest by default
        assert dumps[0]["events"][0]["gseq"] == 9
        gen, dumps, _ = fr.load_dumps(str(tmp_path), generation=0)
        assert dumps[0]["events"][0]["gseq"] == 1


# =====================================================================
# Supervisor emission (gang_diagnosis event) + flight_report CLI
# =====================================================================
class TestGangDiagnosisEvent:
    def _args(self, tmp_path, nprocs=3):
        return types.SimpleNamespace(log_dir=str(tmp_path),
                                     node_rank=0, nproc_per_node=nprocs)

    def test_json_event_carries_structured_verdict(self, tmp_path,
                                                   monkeypatch):
        import paddle_tpu.distributed.launch.__main__ as launch_main
        for r, evs in ((0, [_ev(2, "all_reduce", status="in_flight",
                                t0=12.0)]),
                       (1, [_ev(1, "all_reduce")])):
            with open(tmp_path / f"flightdump.{r}.0.json", "w") as f:
                json.dump(_dump(r, evs, world=3), f)
        monkeypatch.setenv("PADDLE_LOG_JSON", "1")
        monkeypatch.delenv("PADDLE_FLIGHT_DUMP_DIR", raising=False)
        buf = io.StringIO()
        diag = launch_main._emit_flight_diagnosis(
            self._args(tmp_path), 0, 3, stream=buf)
        rec_ = json.loads(buf.getvalue())
        assert rec_["component"] == "launch"
        assert rec_["event"] == "gang_diagnosis"
        assert rec_["desync"] is True
        assert rec_["stragglers"] == diag["stragglers"] == [1, 2]
        assert rec_["ranks_missing_dump"] == [2]
        assert rec_["stuck"]["op"] == "all_reduce"
        assert "never entered" in rec_["message"]

    def test_no_dumps_is_silent(self, tmp_path, monkeypatch):
        import paddle_tpu.distributed.launch.__main__ as launch_main
        monkeypatch.delenv("PADDLE_FLIGHT_DUMP_DIR", raising=False)
        buf = io.StringIO()
        assert launch_main._emit_flight_diagnosis(
            self._args(tmp_path, nprocs=2), 0, 2, stream=buf) is None
        assert buf.getvalue() == ""

    def test_flight_report_cli_matches_shared_impl(self, tmp_path,
                                                   capsys):
        for r in range(2):
            with open(tmp_path / f"flightdump.{r}.0.json", "w") as f:
                json.dump(_dump(r, [_ev(1, "all_reduce",
                                        status="in_flight", t0=2.0)]),
                          f)
        tool = _load_tool("flight_report")
        rc = tool.main([str(tmp_path)])
        out = capsys.readouterr().out
        text, _ = fr.diagnose_dir(str(tmp_path))
        assert rc == 0 and out == text + "\n"       # byte-for-byte
        rc = tool.main([str(tmp_path), "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert doc["desync"] is True
        rc = tool.main([str(tmp_path / "empty")])
        assert rc == 2


# =====================================================================
# Cluster snapshot over TCPStore (heartbeat-style aggregation)
# =====================================================================
@needs_native
class TestClusterSnapshot:
    def test_publish_and_aggregate(self):
        srv = TCPStoreServer(0)
        try:
            store = TCPStore("127.0.0.1", srv.port, timeout_s=5.0)
            recs = {r: fr.FlightRecorder(ring=16, rank=r, world=3)
                    for r in range(2)}
            recs[0].end(recs[0].start("all_reduce", group="mp"))
            recs[1].start("all_reduce", group="mp")   # left hanging
            for r in recs.values():
                assert fr.publish_snapshot(store, rec=r)
            snap = fr.cluster_snapshot(
                lambda t: TCPStore("127.0.0.1", srv.port, timeout_s=t),
                world=3)
            assert snap[0]["groups"]["mp"]["seq"] == 1
            assert snap[1]["groups"]["mp"]["in_flight_op"] == \
                "all_reduce"
            assert snap[1]["in_flight"] == 1
            assert snap[2] is None                   # never published
            store.close()
        finally:
            srv.stop()

    def test_disabled_recorder_publishes_nothing(self):
        srv = TCPStoreServer(0)
        try:
            store = TCPStore("127.0.0.1", srv.port, timeout_s=5.0)
            off = fr.FlightRecorder(ring=0)
            assert fr.publish_snapshot(store, rec=off) is False
            # module-level maybe_publish with no recorder configured
            fr.reset()
            assert fr.maybe_publish(store) is False
            assert store.get("fr/0") is None
            store.close()
        finally:
            srv.stop()


# =====================================================================
# Perfetto export (pid per rank)
# =====================================================================
class TestPerfettoExport:
    def test_pid_per_rank_trace(self, tmp_path):
        from paddle_tpu.inference.telemetry import validate_chrome_trace
        dumps = {
            0: _dump(0, [_ev(1, "all_reduce"),
                         _ev(2, "all_reduce", status="in_flight",
                             t0=12.0)], t_mono=50.0),
            1: _dump(1, [_ev(1, "all_reduce")], t_mono=51.0),
        }
        path = str(tmp_path / "flight_trace.json")
        assert fr.export_chrome_tracing(dumps, path) == path
        doc = validate_chrome_trace(path)
        evs = doc["traceEvents"]
        names = {e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names == {"rank 0 flight recorder",
                         "rank 1 flight recorder"}
        flights = [e for e in evs if e["ph"] == "X"
                   and e.get("args", {}).get("status") == "in_flight"]
        assert flights and flights[0]["pid"] == 0
        # the in-flight op is drawn to rank 0's dump time: 38s
        assert flights[0]["dur"] == pytest.approx(38e6, rel=1e-3)
        assert any(e["ph"] == "i" and "dump" in e["name"] for e in evs)

    def test_export_from_dir_and_empty_raises(self, tmp_path):
        with pytest.raises(ValueError, match="no flight dumps"):
            fr.export_chrome_tracing(str(tmp_path), str(tmp_path / "t"))
        with open(tmp_path / "flightdump.0.0.json", "w") as f:
            json.dump(_dump(0, [_ev(1, "barrier")], world=1), f)
        fr.export_chrome_tracing(str(tmp_path),
                                 str(tmp_path / "t.json"))
        assert os.path.exists(tmp_path / "t.json")


# =====================================================================
# End to end: fault-injected desync on the gloo path
# =====================================================================
DESYNC_E2E = """
import os, sys, time
os.environ["PADDLE_WATCHDOG_TIMEOUT_S"] = "8"
os.environ["PADDLE_HEARTBEAT_INTERVAL_S"] = "0.2"
os.environ["PADDLE_WATCHDOG_KILL_GRACE_S"] = "1"
if os.environ["PADDLE_TRAINER_ID"] == "0":
    # rank 0 (the coordinator): heartbeat dark from the start (the
    # watchdog's lever) AND wedge at the 4th collective entry (the
    # flight recorder's lever — the hang fires INSIDE the choke point,
    # before the entry records, so rank 0's dump shows seq=3 done and
    # never-entered seq=4). The COORDINATOR is the straggler on
    # purpose: a non-coordinator rank that outlives the coordinator is
    # aborted by jax's coordination client before the supervisor can
    # SIGTERM it (that path — no dump at all — is covered by the
    # missing-dump naming in the diagnosis unit tests).
    os.environ["PADDLE_FI_DROP_HEARTBEAT"] = "0"
    os.environ["PADDLE_FI_HANG"] = "0"
    os.environ["PADDLE_FI_AT_POINT"] = "collective"
    os.environ["PADDLE_FI_AT_STEP"] = "3"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

env = dist.init_parallel_env()
assert env.world_size == 2
t = paddle.to_tensor(np.ones((4,), np.float32))
for i in range(50):
    dist.all_reduce(t)          # rank 0 wedges at i == 3; rank 1 then
    time.sleep(0.05)            # blocks INSIDE the gloo collective
print("completed all collectives", flush=True)   # must never print
"""


@needs_native
class TestDesyncEndToEnd:
    def test_hang_produces_dumps_and_named_straggler(self, tmp_path):
        """Acceptance: a fault-injected hang in one rank produces
        per-rank flightdump files and a supervisor report naming the
        desynced collective (op + seq + group), the stuck rank, and its
        in-collective stack — all bounded, no sleeps-as-sync."""
        from paddle_tpu.distributed.resilience import WATCHDOG_EXIT_CODE
        r = run_launch(tmp_path, DESYNC_E2E, ["--nproc_per_node", "2"])
        # rank 1 (wedged INSIDE the collective) escalates via the
        # watchdog once rank 0's heartbeats never arrive
        assert r.returncode == WATCHDOG_EXIT_CODE, (r.stdout, r.stderr)
        log = tmp_path / "log"
        # --- per-rank dumps exist
        d0p, d1p = (log / "flightdump.0.0.json",
                    log / "flightdump.1.0.json")
        assert d0p.exists() and d1p.exists(), list(log.iterdir())
        d0 = json.loads(d0p.read_text())
        d1 = json.loads(d1p.read_text())
        assert d1["reason"] == "peer_failure"       # watchdog trigger
        assert d0["reason"] == "sigterm"            # supervisor reap
        # --- rank 1: the collective is in flight at seq 4, and its
        # main thread stack is inside the collective call
        evs1 = {(e["op"], e["gseq"]): e["status"] for e in d1["events"]
                if e["kind"] == "collective"}
        assert evs1[("all_reduce", 4)] == "in_flight"
        assert evs1[("all_reduce", 3)] == "done"
        main1 = next(v for k, v in d1["stacks"].items()
                     if k.endswith("[main]"))
        assert any("all_reduce" in (fs.get("code") or "")
                   or "all_reduce" in fs.get("func", "")
                   for fs in main1), main1
        # --- rank 0 (the straggler): completed seq 3, never entered 4,
        # and its stack shows the injected hang inside the choke point
        evs0 = [e for e in d0["events"] if e["kind"] == "collective"]
        assert max(e["gseq"] for e in evs0) == 3
        assert all(e["status"] == "done" for e in evs0)
        main0 = next(v for k, v in d0["stacks"].items()
                     if k.endswith("[main]"))
        assert any(fs.get("func") == "inject" for fs in main0), main0
        # --- dump headers are self-describing
        assert d1["generation"] == 0 and d1["world"] == 2
        assert d1["watchdog"]["failure_ranks"] == [0]
        assert d1["watchdog"]["gauges"]["heartbeat_age_s"]
        # --- the supervisor report names op + seq + group + straggler
        assert "flight recorder: cross-rank diagnosis (generation 0, " \
            "world 2)" in r.stderr
        assert "group 'default': desync in all_reduce at seq 4" \
            in r.stderr
        assert "rank 1: in_flight in all_reduce seq=4 for" in r.stderr
        assert "rank 0: completed seq=3, never entered all_reduce " \
            "seq=4" in r.stderr
        assert "stragglers: rank 0" in r.stderr
        assert "straggler rank 0 main-thread stack" in r.stderr
        # --- tools/flight_report.py reproduces it byte-for-byte
        tool = _load_tool("flight_report")
        import contextlib
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            assert tool.main([str(log)]) == 0
        assert buf.getvalue() in r.stderr           # identical block
