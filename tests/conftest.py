"""Test config: force pure-CPU jax with an 8-device virtual mesh so every
parallelism test runs without TPU hardware (SURVEY §4's multi-process-on-one-
host equivalence pattern, realized as multi-device-on-CPU).

Tests never touch a chip (a chip belongs to one process at a time, and the
suite starts children): XLA_FLAGS is set before first backend init and
jax_platforms is forced to cpu via jax.config, which wins over the env. The
chip is exercised by chip_smoke.py only.

For whoever writes the next model's tests:

**Every test has a limit.** ``LIMIT`` seconds after a test's set-up starts, a
watchdog thread in C (``faulthandler.dump_traceback_later(exit=True)``) prints
every thread's stack to the real stderr and ends the process, whether the
main thread is in Python or waits inside XLA, a lock or a child's pipe. Under
xdist the worker shows as ``node down``, the test it ran is failed by name
and a new worker takes the rest of the queue; without xdist the run ends
there. The end of a session has ``END_LIMIT`` the same way. There is no
option to raise it: a test that needs minutes is marked ``slow``. A test
that starts a child uses ``paddle_tpu.testing.child`` (``Child``,
``run_child``, ``run_launch``): its waits have deadlines below ``LIMIT`` and
it kills the child's process group when the ``with`` block ends. Send
``SIGUSR1`` to a controller or a ``gw`` worker to read where it stands.

**Build the tiny model once a module, trace once a shape.** ``--dist
loadfile`` makes each file one serial chain on one worker, so the run is never
shorter than its longest file, and a model file's time is its traces and
compiles, not its arithmetic: eager, a whole model is some hundreds of
one-operation compiles. Run its forward and backward as ONE compiled program
(``paddle.jit.to_static``, the oracle under ``jax.jit``), run each compiled
training step once in a module-scoped fixture that returns numbers and arrays,
and let the tests read them (``tests/test_qwen3_next_model.py``). A new
model's file has 60 s as a chain under ``-n 6`` and no test over 40 s. Nothing
keeps the model past the module (a global, a cache, a thread):
``pytest_runtest_setup`` below fails the next file's first test when a
parameter outlives its file, because a compiled step's state is every live
parameter of the process (ROADMAP.md, Design, D15).
"""
import faulthandler
import gc
import os
import signal

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu"

import numpy as np
import pytest

# Seconds one test may take, set-up, teardown and the finalizers of the
# worker's last test included: about six times the slowest test under -n 6.
LIMIT = 240.0
# Seconds the end of a session may take, in a worker and in the controller.
END_LIMIT = 60.0

_real_stderr = [None]


def pytest_configure(config):
    # fd 2 is the real stderr here (capture is suspended) and a capture
    # file while a test runs, where stacks would be lost with the process
    _real_stderr[0] = os.dup(2)
    faulthandler.register(signal.SIGUSR1, file=_real_stderr[0],
                          all_threads=True)


def _arm(seconds):
    faulthandler.dump_traceback_later(seconds, exit=True,
                                      file=_real_stderr[0])


@pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    _arm(LIMIT)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(tryfirst=True)
def pytest_sessionfinish(session):
    _arm(END_LIMIT)


@pytest.hookimpl(tryfirst=True)
def pytest_unconfigure(config):
    if hasattr(config, "workerinput"):
        # a worker that is done idles until the controller ends the run,
        # and is killed by it 10 s after that
        faulthandler.cancel_dump_traceback_later()
    else:
        # left armed: the interpreter's own exit (the threads it joins,
        # atexit) is part of the end
        _arm(END_LIMIT)


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """The driver's ``--dist loadfile`` with what a worker's death needs:
    xdist 3.8's scope scheduler puts ALL of a dead worker's files back in the
    queue, the finished ones too and the one with the test that killed it.
    The replacement is then handed a finished file, reports nothing, is never
    looked at again, and the controller waits for good (or it is handed the
    fatal test once more). And a replacement that has not reported its
    collection yet is a ``KeyError`` whenever another worker goes down
    meanwhile. And a replacement is handed ONE file; if that has one test,
    the worker holds it until it hears what follows, which is nothing. Here
    only unfinished files go back, the fatal test counts as run (the
    controller fails it by name), a node gets work once its collection has
    arrived, and then until it has three tests pending or the queue is
    empty."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class AfterADeath(LoadFileScheduling):
        def _reschedule(self, node):
            # until nothing more is handed out: enough pending, or none left
            # and the node told to finish (a worker runs a test only once it
            # knows what follows it)
            handed = node in self.registered_collections
            while handed:
                before = len(self.workqueue)
                super()._reschedule(node)
                handed = len(self.workqueue) < before

        def remove_node(self, node):
            fatal = None
            for scope, tests in self.assigned_work.pop(node).items():
                left = [name for name, done in tests.items() if not done]
                if left and fatal is None:
                    fatal = left.pop(0)
                    tests[fatal] = True
                if left:
                    self.workqueue[scope] = tests
            for other in self.assigned_work:
                self._reschedule(other)
            return fatal
    return AfterADeath(config, log)


# uid of a persistent tensor that a test file's import left alive -> the file
_made_at_import = {}
_file = [None]


def pytest_collectreport(report):
    if report.nodeid.endswith(".py"):
        from paddle_tpu.tensor.tensor import persistent_tensors
        for t in persistent_tensors():
            _made_at_import.setdefault(t._uid, report.nodeid)


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    """At a test file's first test: no compiled step of this file may see
    another file's parameters (``jit.to_static`` takes every live persistent
    tensor of the process as its state). What the file before left alive is
    named, fails THIS test, and leaves the registry, so that it costs one
    failure and not one in every file that follows on this worker. Process-
    wide by design, and allowed: this thread's global RNG key
    (``core/rng.py``). A tensor that a test file's import made stays, and
    fails every file's first test until the file builds it in a fixture."""
    if item.path == _file[0]:
        return
    before, _file[0] = _file[0], item.path
    from paddle_tpu.core.rng import _rng
    from paddle_tpu.tensor.tensor import (persistent_tensors,
                                          unregister_persistent_many)
    gc.collect()
    strays = [t for t in persistent_tensors() if t is not _rng.key_tensor]
    if not strays:
        return
    t = strays[0]
    where = _made_at_import.get(t._uid)
    unregister_persistent_many(
        [s for s in strays if s._uid not in _made_at_import])
    pytest.fail(
        f"{len(strays)} persistent tensors outlive their test file; the "
        f"first has shape {tuple(t.shape)} {t.dtype} and was made "
        + (f"by the import of {where}" if where else
           f"in {before.name if before else 'no test file'} or a file "
           "before it on this worker")
        + ": release the module global, cache or thread that holds it",
        pytrace=False)


@pytest.fixture(autouse=True)
def _no_fault_injection_leak(request):
    """Fail FAST if a fault-injection env var leaks into a non-FT test:
    an armed harness silently changes behavior (or kills the worker) far
    from the test that set it. FT tests pass the PADDLE_FI_* vars to
    their SUBPROCESS env only; the pytest process itself must stay clean
    everywhere except tests/test_fault_tolerance.py."""
    from paddle_tpu.testing import (fi_env_active, fr_env_active,
                                    gw_env_active, quant_env_active)
    fspath = str(request.node.fspath)
    exempt = ("test_fault_tolerance" in fspath
              or "test_flight_recorder" in fspath)
    leaked = fi_env_active()
    if leaked and not exempt:
        pytest.fail(
            f"fault-injection env leaked into a non-FT test: {leaked} "
            "(unset PADDLE_FI_*, or pass it to the companion subprocess "
            "env instead of the pytest process)", pytrace=False)
    # flight-recorder config leaks are the same bug class: an armed
    # recorder silently changes what every later collective records and
    # where dumps land — only the flight/FT suites may set these (and
    # they do it via monkeypatch or subprocess envs)
    leaked_fr = fr_env_active()
    if leaked_fr and not exempt:
        pytest.fail(
            f"flight-recorder env leaked into an unrelated test: "
            f"{leaked_fr} (unset PADDLE_FLIGHT_*, or pass it to the "
            "companion subprocess env instead of the pytest process)",
            pytrace=False)
    # gateway/router config leaks (serving_cluster): a leaked policy or
    # heartbeat threshold silently changes placement and failover in
    # every later cluster test — only the cluster suite may set these,
    # and it does so via monkeypatch or constructor args
    leaked_gw = gw_env_active()
    if leaked_gw and "test_serving_cluster" not in fspath:
        pytest.fail(
            f"gateway env leaked into an unrelated test: {leaked_gw} "
            "(unset PADDLE_GATEWAY_*/PADDLE_ROUTER_*, or pass them via "
            "monkeypatch / constructor args inside the cluster suite)",
            pytrace=False)
    # serving-quant config leaks (PADDLE_TPU_DECODE_*): a leaked weight
    # flavor silently re-stacks every later engine's weights and a
    # leaked cache flavor flips every later pool to int8 — quant tests
    # set these via monkeypatch (invisible here: this fixture reads the
    # env BEFORE the test body) or the weight_quant=/kv_quant= ctor
    # args, so any hit is a genuine cross-test leak
    leaked_q = quant_env_active()
    if leaked_q and "test_quant_serving" not in fspath:
        pytest.fail(
            f"serving-quant env leaked into an unrelated test: "
            f"{leaked_q} (unset PADDLE_TPU_DECODE_*, or use monkeypatch "
            "/ the weight_quant=/kv_quant= constructor args)",
            pytrace=False)
    yield


def check_serving_metrics(eng):
    """Metrics-consistency guard for serving tests (same spirit as the
    fault-injection leak guard: invariants that must hold for ANY engine
    state are asserted in one place). Window counters must reconcile:
    every admission is exactly one prefix-cache lookup (hit or miss),
    token throughput implies busy time, and rates stay in [0, 1].
    Returns the metrics dict so tests can chain their own assertions.

    NOTE: call on windows without an intervening reset_metrics() while
    requests were still in flight — a request admitted before the reset
    that finishes after it counts in the finished window but not the
    admitted one, which legitimately breaks the reconciliation."""
    m = eng.metrics()
    assert m["requests_admitted"] >= 0
    # every finished request was admitted, forked, MIGRATED IN, or
    # RESUMED from the QoS parking lot (expired ones may have been shed
    # straight from the queue, so they don't reconcile this way; forks,
    # migrated-in sessions, and resumes are not admissions — they
    # perform no prefix lookup and count separately, so hits + misses
    # == admitted stays exact)
    assert m["requests_finished"] <= \
        m["requests_admitted"] + m["requests_forked"] \
        + m["requests_migrated_in"] + m["requests_resumed"]
    # QoS preemption reconciliation: a resume re-imports a previously
    # preempted session, so resumed can never lead preempted; the
    # parking-lot gauge is exactly the not-yet-resumed (and not yet
    # expired) preemptions still holding their host-RAM state
    assert 0 <= m["requests_resumed"] <= m["requests_preempted"]
    assert 0 <= m["requests_parked"] <= \
        m["requests_preempted"] - m["requests_resumed"]
    if getattr(eng, "pool", None) is None:
        assert m["requests_preempted"] == 0    # preemption is paged-only
    # per-class split: every admission and every emitted token carries
    # exactly one QoS class, so the class counters must sum to the
    # totals — true with zero QoS traffic (all-default runs land every
    # count in "normal")
    adm_by_class = (m["requests_admitted_high"],
                    m["requests_admitted_normal"],
                    m["requests_admitted_low"])
    assert sum(adm_by_class) == m["requests_admitted"], (
        f"per-class admissions don't sum: {adm_by_class} != "
        f"{m['requests_admitted']}")
    tok_by_class = (m["tokens_emitted_high"],
                    m["tokens_emitted_normal"],
                    m["tokens_emitted_low"])
    assert sum(tok_by_class) == m["tokens_emitted"], (
        f"per-class tokens don't sum: {tok_by_class} != "
        f"{m['tokens_emitted']}")
    # live-migration counters only move on paged engines (the payload
    # IS pool blocks)
    assert m["requests_migrated_in"] >= 0
    assert m["requests_migrated_out"] >= 0
    if getattr(eng, "pool", None) is None:
        assert m["requests_migrated_in"] == 0
        assert m["requests_migrated_out"] == 0
    # disaggregated-handoff counters are paged-only the same way (the
    # shipped payload IS pool blocks), and the role label is always one
    # of the three placement classes
    assert m["kv_blocks_shipped"] >= 0
    assert m["kv_blocks_adopted"] >= 0
    assert m["role"] in ("prefill", "decode", "mixed")
    if getattr(eng, "pool", None) is None:
        assert m["kv_blocks_shipped"] == 0
        assert m["kv_blocks_adopted"] == 0
    if getattr(eng, "prefix_cache", None) is not None:
        assert m["prefix_hits"] + m["prefix_misses"] == \
            m["requests_admitted"], (
            f"every admission must count as exactly one prefix lookup: "
            f"hits={m['prefix_hits']} + misses={m['prefix_misses']} != "
            f"admitted={m['requests_admitted']}")
        assert m["prefill_tokens_saved"] >= 0
        assert m["prefill_tokens_computed"] >= 0
        if m["prefix_hits"] == 0:
            assert m["prefill_tokens_saved"] == 0
        st = m["prefix_store"]
        assert 0 <= st["blocks_used"] <= st["blocks_capacity"]
        assert st["blocks_used"] + st["blocks_free"] == \
            st["blocks_capacity"]
    else:
        assert m["prefix_hits"] == 0 and m["prefix_misses"] == 0
        assert m["prefill_tokens_saved"] == 0
        assert m["prefill_tokens_computed"] == 0
    if m["prefix_hit_rate"] is not None:
        assert 0.0 <= m["prefix_hit_rate"] <= 1.0
    # speculative-decoding reconciliation: a draft token can only be
    # accepted after being proposed, and every emitted token is either
    # one per-row sample event (admit/decode/verify step) or an
    # accepted draft riding a verify step — the engine counts them so
    # this holds in greedy AND sampled mode, spec on or off
    assert 0 <= m["draft_accepted"] <= m["draft_proposed"]
    assert m["tokens_emitted"] == m["decode_steps"] + \
        m["draft_accepted"], (
        f"token accounting broke: tokens={m['tokens_emitted']} != "
        f"steps={m['decode_steps']} + accepted={m['draft_accepted']}")
    if m["acceptance_rate"] is not None:
        assert 0.0 <= m["acceptance_rate"] <= 1.0
    if m["tokens_per_step"] is not None:
        assert m["tokens_per_step"] >= 1.0
    if getattr(eng, "spec_k", 0) == 0:
        assert m["draft_proposed"] == 0 and m["draft_accepted"] == 0
    if m["tokens_emitted"]:
        assert m["busy_s"] > 0 and m["tokens_per_sec"] > 0
    # token-budget reconciliation: a budget dispatch can never pack
    # more real tokens than steps x token_budget, and every packed
    # token is exactly one of {prefill chunk token, decode input,
    # draft} — the three parts must sum to the total
    tb = getattr(eng, "token_budget", 0)
    assert m["budget_tokens_used"] == (
        m["budget_prefill_tokens"] + m["budget_decode_tokens"]
        + m["budget_draft_tokens"]), (
        f"budget token split broke: {m['budget_tokens_used']} != "
        f"{m['budget_prefill_tokens']} + {m['budget_decode_tokens']} + "
        f"{m['budget_draft_tokens']}")
    # padding = masked/pad positions the budget dispatches actually
    # computed; used + padding is each dispatch's real compute width,
    # so the utilization gauge reconstructs from the two counters
    # exactly — true under BOTH the row-aligned and flat layouts, no
    # layout branch needed
    assert m["budget_padding_tokens"] >= 0
    if tb:
        assert m["budget_tokens_used"] <= m["budget_steps"] * tb, (
            f"budget overspent: {m['budget_tokens_used']} tokens in "
            f"{m['budget_steps']} steps at budget {tb}")
        if m["budget_utilization"] is not None:
            assert 0.0 < m["budget_utilization"] <= 1.0
            assert m["budget_utilization"] == round(
                m["budget_tokens_used"]
                / (m["budget_tokens_used"]
                   + m["budget_padding_tokens"]), 4), (
                "budget_utilization no longer reconstructs from "
                "used/(used + padding)")
    else:
        assert m["budget_steps"] == 0 and m["budget_tokens_used"] == 0
        assert m["budget_padding_tokens"] == 0
        assert m["budget_utilization"] is None
    # SLO/goodput reconciliation: every FINISHED request gets exactly
    # one verdict (ok / violated-by-queueing / violated-by-service), so
    # the three counters must sum to requests_finished — with no
    # objectives declared everything is ok
    assert (m["slo_ok"] + m["slo_violated_queue"]
            + m["slo_violated_service"]) == m["requests_finished"], (
        f"SLO accounting broke: ok={m['slo_ok']} + "
        f"queue={m['slo_violated_queue']} + "
        f"service={m['slo_violated_service']} != "
        f"finished={m['requests_finished']}")
    if not getattr(eng, "_slo").enabled:
        assert m["slo_violated_queue"] == 0
        assert m["slo_violated_service"] == 0
    # paged-pool block accounting: the allocator must reconcile on
    # EVERY serving test — used + free == NBtotal (a refcounted block
    # shared by N slot tables and the prefix store is ONE physical
    # block, counted once), used matches the refcount vector, and a
    # positive refcount never rides the free list
    if getattr(eng, "pool", None) is not None:
        pool = eng.pool
        assert m["kv_blocks_total"] == pool.num_blocks
        assert m["kv_blocks_used"] + m["kv_blocks_free"] == \
            m["kv_blocks_total"], (
            f"kv block leak: used={m['kv_blocks_used']} + "
            f"free={m['kv_blocks_free']} != total={m['kv_blocks_total']}")
        assert m["kv_blocks_used"] == int((pool.refcounts > 0).sum())
        assert not any(pool.refcounts[b] for b in pool._free)
        assert 0 <= eng._kv_reserved <= pool.num_blocks
        assert m["kv_cow_copies"] >= 0
        assert pool.used <= pool.used_peak <= pool.num_blocks
        # mesh-sharded pool accounting: shard_count is the mesh's mp
        # degree (1 unsharded), the head split is exact (enforced at
        # construction), and shard_count x per-shard bytes covers the
        # WHOLE pool — per-device residency is dense/mp. Block counts
        # above are deliberately shard-independent: the allocator and
        # tables are replicated host data, one logical pool.
        fmt_heads = eng.dec.fmt.num_heads
        assert m["kv_shard_count"] >= 1
        assert m["kv_shard_heads"] * m["kv_shard_count"] == fmt_heads
        pool_bytes = int(eng._caches["kv"].nbytes)
        if "sc" in eng._caches:
            pool_bytes += int(eng._caches["sc"].nbytes)
        assert m["kv_shard_pool_bytes"] * m["kv_shard_count"] == \
            pool_bytes, (
            f"per-shard pool bytes broke: {m['kv_shard_pool_bytes']} x "
            f"{m['kv_shard_count']} != {pool_bytes}")
    else:
        assert m["kv_blocks_total"] is None
        assert m["kv_cow_copies"] == 0
        assert m["kv_shard_count"] is None
        assert m["kv_shard_heads"] is None
        assert m["kv_shard_pool_bytes"] is None
    # tensor-parallel weight placement: on EVERY engine (sharded or
    # not) the byte identity must be exact — the per-device footprint
    # of the step's weight arrays splits into a sharded part (counted
    # once per device) and a replicated part (same bytes everywhere),
    # and (per_device - replicated) x shard_count + replicated
    # recovers the dense total computed from the arrays themselves.
    # Unsharded engines degenerate to per_device == replicated ==
    # dense with shard_count == 1.
    n_ws = m["weight_shard_count"]
    assert n_ws >= 1
    import math as _math
    dense_w = sum(_math.prod(a.shape) * a.dtype.itemsize
                  for a in eng._weight_arrays())
    assert (m["weight_bytes_per_device"] - m["weight_bytes_replicated"]) \
        * n_ws + m["weight_bytes_replicated"] == dense_w, (
        f"weight byte identity broke: per_device="
        f"{m['weight_bytes_per_device']} replicated="
        f"{m['weight_bytes_replicated']} shards={n_ws} "
        f"dense={dense_w}")
    assert 0 <= m["weight_bytes_replicated"] <= \
        m["weight_bytes_per_device"] <= dense_w
    if n_ws == 1:
        assert m["weight_bytes_per_device"] == dense_w
    # telemetry reconciliation (the PR 8 surface): the histograms ARE
    # the percentile source — latency observes exactly the non-expired
    # finished requests, TTFT at most that (a request always has a
    # first token by finish; <= covers exotic fork edge cases), and a
    # percentile is None exactly when its histogram window is empty
    tele = getattr(eng, "telemetry", None)
    if tele is not None:
        assert tele.hist_latency.count == m["requests_finished"], (
            f"latency histogram saw {tele.hist_latency.count} requests "
            f"but requests_finished={m['requests_finished']}")
        assert tele.hist_ttft.count <= m["requests_finished"]
        assert (m["ttft_p50_s"] is None) == (tele.hist_ttft.count == 0)
        assert (m["latency_p50_s"] is None) == (tele.hist_latency.count
                                                == 0)
        # queue/service decomposition observes exactly the finished set
        # (the SLO layer's cause-attribution source)
        assert tele.hist_queue.count == m["requests_finished"]
        assert tele.hist_service.count == m["requests_finished"]
        assert (m["queue_p50_s"] is None) == (tele.hist_queue.count == 0)
        assert (m["service_p50_s"] is None) == (tele.hist_service.count
                                                == 0)
        for a, b in (("ttft_p50_s", "ttft_p90_s"),
                     ("ttft_p90_s", "ttft_p99_s"),
                     ("latency_p50_s", "latency_p99_s"),
                     ("queue_p50_s", "queue_p99_s"),
                     ("service_p50_s", "service_p99_s")):
            if m[a] is not None:
                assert 0.0 <= m[a] <= m[b], (a, b, m[a], m[b])
        assert m["queue_depth"] >= 0 and 0.0 <= m["occupancy"] <= 1.0
        assert m["requests_rejected"] >= 0 and m["requests_expired"] >= 0
        assert m["traces"] >= 0
        # the ring is BOUNDED (so is the results dict — the old
        # done-list leak), and the exposition round-trips a text-format
        # parse with lifetime counters never lagging the window
        assert len(tele.spans) <= max(tele.ring, 1)
        assert len(tele.steps) <= max(tele.ring, 1)
        assert len(eng.results) <= eng._results_cap
        from paddle_tpu.inference.telemetry import parse_prometheus
        prom = parse_prometheus(eng.metrics_prometheus())
        assert prom["paddle_serving_tokens_emitted_total"] >= \
            m["tokens_emitted"]
        assert prom["paddle_serving_requests_admitted_total"] >= \
            m["requests_admitted"]
        assert prom["paddle_serving_ttft_seconds_count"] >= \
            tele.hist_ttft.count
        assert prom["paddle_serving_request_latency_seconds_count"] >= \
            m["requests_finished"]
    return m


@pytest.fixture
def serving_metrics_ok():
    """Fixture handle on check_serving_metrics for serving tests."""
    return check_serving_metrics


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as paddle
    paddle.seed(102)
    np.random.seed(102)
    yield
    from paddle_tpu.tensor.tensor import clear_tape
    clear_tape()
    # reset global fleet/mesh state: each test starts without an active
    # hybrid mesh (the reference's per-process test isolation); tests that
    # need one call fleet.init themselves
    from paddle_tpu.distributed.fleet.base.topology import _HYBRID_GROUP
    from paddle_tpu.distributed.fleet import _fleet_state
    _HYBRID_GROUP[0] = None
    _fleet_state.update(strategy=None, hcg=None, initialized=False)
    # drop dead persistent tensors NOW: the WeakSet otherwise loses the
    # previous test's leftovers at a nondeterministic GC point, which can
    # change jit.to_static's state-identity cache key between two calls in
    # the NEXT test and break trace-count assertions
    import gc
    gc.collect()
