"""Priority preemption & multi-tenant QoS: graceful degradation under
overload.

Contracts under test (deterministic — virtual clocks, unthreaded
replicas, fault injection via env, no real-time sleeps):

  * PRIORITY classes on ``submit(priority=...)``: validated vocabulary,
    per-class queues drained strict-priority (FIFO within a class),
    all-default workloads identical to the old single FIFO;
  * PREEMPTION-TO-HOST: ``preempt_to_host``/``resume_from_host`` park a
    running slot's full decode state in host RAM and restore it
    token-identically — greedy AND plain-sampled (the seed rides the
    state), mid-decode AND mid-prefill — with exactly-once streaming
    across the park (the harvest cursor survives) and clean pool
    accounting (blocks freed at preempt, reservation re-taken at
    resume, committed never double-counted);
  * the ``_qos_schedule`` pass: a blocked strictly-better queue head
    evicts the lowest-class youngest running victim; parked sessions
    resume best-class-first when pressure clears — nothing is aborted,
    low class is delayed, not dropped;
  * DEADLINES keep running while parked: park time is queue-attributed
    delay, never a budget refill — a parked request expires at its
    original deadline and a resumed one keeps its original t_submit;
  * the WEIGHTED-FAIR prefill packer (``_prefill_allocations``):
    proportional shares + work-conserving spill as pure host data,
    single-class calls exactly FCFS, zero retraces under mixed-class
    churn;
  * ``PADDLE_FI_AT_POINT=preempt`` (the chaos satellite): a crash
    between export and parking-lot insert loses the parked copy — the
    router's classic failover replays the stream exactly-once;
  * the gateway's tenant token buckets / live-request quotas and the
    SLO-aware shed predicate (pure host units — the wire surface is
    pinned in tools/check_http_surface.py).
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference.serving import AdmissionFull, ServingEngine
from paddle_tpu.inference.telemetry import (DEFAULT_QOS_SHARES,
                                            QOS_CLASSES, QOS_DEFAULT)
from paddle_tpu.nn.layer.common import Embedding, Linear
from paddle_tpu.serving_cluster import Gateway, LocalReplica, Router
from paddle_tpu.testing import fault
from paddle_tpu.testing.fault import FaultInjected
from paddle_tpu.testing.oracle import sequential_tokens

V, E, H, FF, L = 97, 32, 4, 64, 2
WAIT_S = 120                              # bound on every drive loop


def _model(seed=3):
    paddle.seed(seed)
    embed = Embedding(V, E)
    fmt = FusedMultiTransformer(E, H, FF, num_layers=L,
                                normalize_before=True)
    head = Linear(E, V, bias_attr=False)
    fmt.eval()
    return fmt, embed, head


def _engine(fmt, embed, head, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_cap", 8)
    return ServingEngine(fmt, embed, head, **kw)


def _oracle(fmt, embed, head, prompt, max_new):
    return sequential_tokens(fmt, embed, head, prompt,
                             max_new_tokens=max_new).tolist()


def _prompt(n=10, seed=3):
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, V, (n,))]


# =====================================================================
# priority classes on submit
# =====================================================================
class TestSubmitPriority:
    def test_vocabulary_and_default(self):
        fmt, embed, head = _model()
        eng = _engine(fmt, embed, head)
        with pytest.raises(ValueError, match="priority"):
            eng.submit(_prompt(6), max_new_tokens=2,
                       priority="platinum")
        rid = eng.submit(_prompt(6), max_new_tokens=2)
        assert eng._req_index[rid].priority == QOS_DEFAULT
        assert eng.queue_depths() == {"high": 0, "normal": 1, "low": 0}
        rid2 = eng.submit(_prompt(6, seed=4), max_new_tokens=2,
                          priority="low")
        assert eng._req_index[rid2].priority == "low"
        assert eng.queue_depths()["low"] == 1
        assert eng.queue_depth == 2        # classes sum to the total
        eng.run()
        assert eng.poll(rid)["state"] == "finished"
        assert eng.poll(rid2)["state"] == "finished"
        m = eng.metrics()
        assert m["requests_admitted_normal"] == 1
        assert m["requests_admitted_low"] == 1
        assert m["requests_admitted_high"] == 0

    def test_strict_priority_admission_order(self):
        """With the only slot busy, a queued HIGH request admits before
        an earlier-queued LOW one — the per-class queues drain in class
        order, not arrival order."""
        fmt, embed, head = _model()
        eng = _engine(fmt, embed, head, num_slots=1)
        # occupy the slot with a HIGH request so the scheduler never
        # preempts it for the queued head
        run_rid = eng.submit(_prompt(8, seed=1), max_new_tokens=10,
                             priority="high")
        low_rid = eng.submit(_prompt(8, seed=2), max_new_tokens=2,
                             priority="low")
        high_rid = eng.submit(_prompt(8, seed=3), max_new_tokens=2,
                              priority="high")
        deadline = time.monotonic() + WAIT_S
        while high_rid in eng._req_index \
                and eng._req_index[high_rid].state == "queued":
            assert time.monotonic() < deadline
            eng.step()
        # the later-arriving high request got the slot first
        assert eng._req_index[low_rid].state == "queued"
        eng.run()
        assert all(eng.poll(r)["state"] == "finished"
                   for r in (run_rid, low_rid, high_rid))


# =====================================================================
# preemption-to-host
# =====================================================================
class TestPreemptResume:
    def test_greedy_preempt_resume_exactly_once(self,
                                                serving_metrics_ok):
        fmt, embed, head = _model()
        prompt = np.asarray(_prompt(12), np.int32)
        base = _engine(fmt, embed, head)
        rid = base.submit(prompt, max_new_tokens=20)
        base.run()
        want = [int(t) for t in base.results[rid]["tokens"]]

        eng = _engine(fmt, embed, head)
        rid = eng.submit(prompt, max_new_tokens=20)
        eng.track(rid)
        deadline = time.monotonic() + WAIT_S
        while len(eng._req_index[rid].tokens) < 3:
            assert time.monotonic() < deadline
            eng.step()
        got, done, _ = eng.harvest_new_tokens(rid)
        assert not done
        committed = eng._kv_committed
        eng.preempt_to_host(rid)
        # slot + physical blocks released, reservation returned; the
        # COMMITTED budget stays (the request still exists)
        assert eng.pool.used == 0
        assert eng._kv_reserved == 0
        assert eng._kv_committed == committed
        assert eng._req_index[rid].state == "preempted"
        assert eng.metrics()["requests_parked"] == 1
        # the stream cursor survives the park: poll sees the live state
        assert eng.poll(rid)["state"] == "preempted"
        # no pressure -> the next step's QoS pass resumes it; run to
        # completion and the stream is exactly-once with full parity
        eng.run()
        new, done, _ = eng.harvest_new_tokens(rid)
        assert done
        assert got + new == want
        assert [int(t) for t in eng.results[rid]["tokens"]] == want
        m = serving_metrics_ok(eng)
        assert m["requests_preempted"] == 1
        assert m["requests_resumed"] == 1
        assert m["requests_parked"] == 0
        assert eng.pool.used == 0 and eng._kv_committed == 0
        # steady state: the FIRST cycle compiled the KV export/import
        # helpers; a second park/resume cycle compiles NOTHING new
        tc = eng._trace_count
        rid2 = eng.submit(prompt, max_new_tokens=20)
        deadline = time.monotonic() + WAIT_S
        while len(eng._req_index[rid2].tokens) < 3:
            assert time.monotonic() < deadline
            eng.step()
        eng.preempt_to_host(rid2)
        eng.run()
        assert [int(t) for t in eng.results[rid2]["tokens"]] == want
        assert eng._trace_count == tc, (
            "park/resume must be retrace-free after the first cycle")

    def test_sampled_preempt_resume_token_identical(self):
        """Plain sampled mode: the per-request seed rides the parked
        state and every draw is fold_in(seed, nt), so the resumed
        continuation matches the never-preempted stream exactly."""
        fmt, embed, head = _model()

        def mk():
            return _engine(fmt, embed, head, do_sample=True, top_k=8,
                           temperature=0.9)
        prompt = np.asarray(_prompt(10, seed=7), np.int32)
        base = mk()
        rid = base.submit(prompt, max_new_tokens=16)
        seed0 = base._req_index[rid].seed
        base.run()
        want = [int(t) for t in base.results[rid]["tokens"]]

        eng = mk()
        rid = eng.submit(prompt, max_new_tokens=16)
        # force the SAME per-request seed as the baseline (each submit
        # draws a fresh one off the global key stream)
        eng._req_index[rid].seed = seed0
        deadline = time.monotonic() + WAIT_S
        while len(eng._req_index[rid].tokens) < 4:
            assert time.monotonic() < deadline
            eng.step()
        eng._rseed[eng._req_index[rid].slot] = seed0
        eng.preempt_to_host(rid)
        assert eng._parked[rid]["seed"] == seed0   # the seed is parked
        eng.run()
        assert [int(t) for t in eng.results[rid]["tokens"]] == want

    def test_preempt_mid_prefill(self, serving_metrics_ok):
        """A slot preempted MID-PREFILL (budget scheduler, pf_left > 0)
        resumes its prefill cursor on the same engine and still matches
        the oracle — no token was ever emitted pre-park."""
        fmt, embed, head = _model()
        eng = _engine(fmt, embed, head, token_budget=8)
        prompt = np.asarray(_prompt(40, seed=11), np.int32)
        want = _oracle(fmt, embed, head, [int(t) for t in prompt], 8)
        rid = eng.submit(prompt, max_new_tokens=8)
        eng.step()                         # some prefill, no tokens yet
        req = eng._req_index[rid]
        assert req.slot is not None and eng._pf_left[req.slot] > 0
        eng.preempt_to_host(rid)
        st = eng._parked[rid]
        assert st["pf_left"] > 0 and not st["tokens"]
        assert eng.pool.used == 0          # partial prefill blocks freed
        eng.run()
        assert [int(t) for t in eng.results[rid]["tokens"]] == want
        m = serving_metrics_ok(eng)
        assert m["requests_preempted"] == 1 and m["requests_resumed"] == 1

    def test_deadline_keeps_running_while_parked(self,
                                                 serving_metrics_ok):
        """Park time burns deadline budget: a parked request expires at
        its ORIGINAL deadline, and a resumed one keeps its original
        t_submit — the park/resume cycle never refills the clock."""
        fmt, embed, head = _model()
        clock = [0.0]

        def tick():
            clock[0] += 1e-3
            return clock[0]

        eng = _engine(fmt, embed, head, num_slots=1, clock=tick)
        # --- half 1: expire IN the parking lot
        rid = eng.submit(_prompt(8, seed=1), max_new_tokens=30,
                         deadline_s=5.0)
        deadline = time.monotonic() + WAIT_S
        while len(eng._req_index[rid].tokens) < 2:
            assert time.monotonic() < deadline
            eng.step()
        t_submit0 = eng._req_index[rid].t_submit
        eng.preempt_to_host(rid)
        clock[0] += 10.0                   # parked past the deadline
        eng.step()                         # the expiry sweep runs first
        assert eng.poll(rid)["state"] == "expired"
        assert rid not in eng._parked      # the lot is cleaned up
        m = serving_metrics_ok(eng)
        assert m["requests_expired"] == 1
        assert m["requests_resumed"] == 0
        assert eng.pool.used == 0 and eng._kv_committed == 0

        # --- half 2: resume preserves t_submit, and the time spent
        # parked still counts against the same deadline
        rid2 = eng.submit(_prompt(8, seed=2), max_new_tokens=30,
                          deadline_s=5.0)
        while len(eng._req_index[rid2].tokens) < 2:
            assert time.monotonic() < deadline
            eng.step()
        t_submit1 = eng._req_index[rid2].t_submit
        assert t_submit1 > t_submit0
        eng.preempt_to_host(rid2)
        clock[0] += 3.0                    # parked 3 of the 5 seconds
        eng.step()                         # QoS pass resumes it
        req2 = eng._req_index[rid2]
        assert req2.state == "running"
        assert req2.t_submit == t_submit1  # no budget refill
        assert req2.deadline_s == 5.0
        clock[0] += 3.0                    # 6s total > the 5s deadline
        eng.step()
        assert eng.poll(rid2)["state"] == "expired"
        m = serving_metrics_ok(eng)
        assert m["requests_resumed"] == 1
        assert m["requests_expired"] == 2


# =====================================================================
# the QoS scheduling pass
# =====================================================================
class TestQosScheduling:
    def test_high_preempts_low_then_low_resumes(self,
                                                serving_metrics_ok):
        """The graceful-degradation contract: under slot pressure a
        queued HIGH request evicts the running LOW one to host RAM; the
        low request resumes when the slot frees and BOTH finish with
        exact greedy parity — delayed, never dropped."""
        fmt, embed, head = _model()
        low_prompt, high_prompt = _prompt(10, seed=5), _prompt(10, seed=6)
        want_low = _oracle(fmt, embed, head, low_prompt, 12)
        want_high = _oracle(fmt, embed, head, high_prompt, 8)
        eng = _engine(fmt, embed, head, num_slots=1)
        low = eng.submit(np.asarray(low_prompt, np.int32),
                         max_new_tokens=12, priority="low")
        eng.track(low)
        deadline = time.monotonic() + WAIT_S
        while len(eng._req_index[low].tokens) < 3:
            assert time.monotonic() < deadline
            eng.step()
        got_low = eng.harvest_new_tokens(low)[0]
        high = eng.submit(np.asarray(high_prompt, np.int32),
                          max_new_tokens=8, priority="high")
        eng.step()                         # the pass evicts low for high
        assert eng._req_index[low].state == "preempted"
        assert eng._req_index[high].state == "running"
        assert eng.metrics()["requests_parked"] == 1
        eng.run()
        # nothing aborted: both streams finished token-identically
        assert [int(t) for t in
                eng.results[high]["tokens"]] == want_high
        new, done, _ = eng.harvest_new_tokens(low)
        assert done and got_low + new == want_low
        m = serving_metrics_ok(eng)
        assert m["requests_preempted"] == 1
        assert m["requests_resumed"] == 1
        assert m["requests_finished"] == 2
        assert m["requests_expired"] == 0
        assert m["tokens_emitted_high"] == 8
        assert m["tokens_emitted_low"] == 12
        assert eng.pool.used == 0 and eng._kv_committed == 0

    def test_equal_class_never_preempts(self):
        """Pressure from an EQUAL-class head must queue, not evict —
        preemption needs a strictly better class."""
        fmt, embed, head = _model()
        eng = _engine(fmt, embed, head, num_slots=1)
        a = eng.submit(_prompt(8, seed=1), max_new_tokens=6,
                       priority="normal")
        b = eng.submit(_prompt(8, seed=2), max_new_tokens=4,
                       priority="normal")
        deadline = time.monotonic() + WAIT_S
        while a in eng._req_index \
                and eng._req_index[a].state == "queued":
            assert time.monotonic() < deadline
            eng.step()
        # b pressures the only slot the whole time a runs — and never
        # evicts it
        eng.run()
        assert eng.metrics()["requests_preempted"] == 0
        assert eng.poll(a)["state"] == "finished"
        assert eng.poll(b)["state"] == "finished"

    def test_prefill_allocations_weighted_fair(self):
        """The packer math, pinned: proportional shares for classes
        with demand, FCFS within a class, work-conserving spill of
        leftover budget, and the single-class path EXACTLY the old
        FCFS packing (pure host data — no dispatch shape depends on
        it)."""
        fmt, embed, head = _model()
        eng = _engine(fmt, embed, head, token_budget=8, num_slots=3)
        rids = [eng.submit(np.asarray(_prompt(40, seed=s), np.int32),
                           max_new_tokens=2, priority=p)
                for s, p in ((1, "high"), (2, "normal"), (3, "low"))]
        eng.step()                         # assign slots, start prefill
        slot = {p: eng._req_index[r].slot
                for r, p in zip(rids, ("high", "normal", "low"))}
        assert all(s is not None for s in slot.values())
        # fabricate ample demand so the split is exactly the shares
        for s in slot.values():
            eng._pf_left[s] = 100
        rows = list(slot.values())
        shares = DEFAULT_QOS_SHARES        # high=4 normal=2 low=1
        assert eng.qos_shares == shares
        allocs, left = eng._prefill_allocations(rows, 14)
        assert dict(allocs) == {slot["high"]: 8, slot["normal"]: 4,
                                slot["low"]: 2}
        assert left == 0
        # work-conserving: high's demand collapses, its unused share
        # spills to the next class instead of idling
        eng._pf_left[slot["high"]] = 2
        allocs, left = eng._prefill_allocations(rows, 14)
        assert dict(allocs) == {slot["high"]: 2, slot["normal"]: 10,
                                slot["low"]: 2}
        assert left == 0
        # col_cap bounds every row (the row-aligned layout's column
        # budget) before shares are applied
        eng._pf_left[slot["high"]] = 100
        allocs, _ = eng._prefill_allocations(rows, 14, col_cap=3)
        assert all(n <= 3 for _s, n in allocs)
        # single class present -> exactly the old FCFS packing: first
        # rid takes the whole budget, nothing proportional
        solo = _engine(fmt, embed, head, token_budget=8, num_slots=2)
        r1 = solo.submit(np.asarray(_prompt(40, seed=4), np.int32),
                         max_new_tokens=2)
        r2 = solo.submit(np.asarray(_prompt(40, seed=5), np.int32),
                         max_new_tokens=2)
        solo.step()
        s1, s2 = (solo._req_index[r].slot for r in (r1, r2))
        solo._pf_left[s1] = solo._pf_left[s2] = 100
        allocs, left = solo._prefill_allocations([s1, s2], 10)
        first = min((s1, s2), key=lambda s: solo._slot_req[s].rid)
        assert allocs == [(first, 10)]
        assert left == 0
        # the engines carry fabricated pf_left — do NOT drive them on

    def test_mixed_class_budget_run_zero_retraces(self):
        """Mixed-class churn under the budget scheduler reshapes only
        HOST data: after a single-class warmup, running high/normal/low
        traffic (with a preemption in the mix) compiles nothing new."""
        fmt, embed, head = _model()
        eng = _engine(fmt, embed, head, token_budget=16, num_slots=2)
        for s in (1, 2):
            eng.submit(np.asarray(_prompt(20, seed=s), np.int32),
                       max_new_tokens=4)
        eng.run()
        tc = eng._trace_count
        for s, p in ((3, "low"), (4, "high"), (5, "normal"),
                     (6, "high")):
            eng.submit(np.asarray(_prompt(20, seed=s), np.int32),
                       max_new_tokens=4, priority=p)
        eng.run()
        m = eng.metrics()
        assert m["requests_finished"] == 6
        assert eng._trace_count == tc, (
            "QoS scheduling must be pure host data — it retraced")

    def test_parse_qos_shares(self):
        parse = ServingEngine._parse_qos_shares
        assert parse("") == DEFAULT_QOS_SHARES
        assert parse("high=8,low=3") == {"high": 8, "normal": 2,
                                         "low": 3}
        with pytest.raises(ValueError):
            parse("gold=2")
        with pytest.raises(ValueError):
            parse("high=0")


# =====================================================================
# the preempt fault point: crash between export and park
# =====================================================================
class TestPreemptFault:
    def test_preempt_crash_falls_back_to_failover(
            self, monkeypatch, serving_metrics_ok):
        """The chaos satellite: PADDLE_FI_AT_POINT=preempt raises AFTER
        the slot is freed but BEFORE the parking-lot insert — the
        parked copy is lost with the replica, and the router's classic
        failover replays the stream elsewhere exactly-once (delivered
        prefix skipped)."""
        fmt, embed, head = _model()
        clock = [0.0]
        reps = [LocalReplica(f"replica{i}",
                             _engine(fmt, embed, head, num_slots=1),
                             threaded=False, clock=lambda: clock[0])
                for i in range(2)]
        router = Router(reps, policy="round_robin", hb_dead_s=1.0,
                        snap_max_age_s=0.0, clock=lambda: clock[0])
        prompt = _prompt(10)
        want = _oracle(fmt, embed, head, prompt, 20)
        gid = router.submit(prompt, max_new_tokens=20, priority="low")
        victim = router._table[gid].replica
        vrep = router.replicas[victim]
        got = []
        deadline = time.monotonic() + WAIT_S
        while len(got) < 3:
            assert time.monotonic() < deadline
            vrep.pump()
            got += router.harvest(gid)[0]
        # pressure: a strictly better head blocked on the only slot —
        # the next step's QoS pass preempts the low victim, and the
        # armed fault kills the replica inside that window
        vrep.engine.submit(np.asarray(_prompt(8, seed=9), np.int32),
                           max_new_tokens=4, priority="high")
        fault.reset()
        monkeypatch.setenv("PADDLE_FI_AT_POINT", "preempt")
        monkeypatch.setenv("PADDLE_FI_RAISE", "0")
        try:
            with pytest.raises(FaultInjected):
                vrep.pump()
        finally:
            monkeypatch.delenv("PADDLE_FI_AT_POINT")
            monkeypatch.delenv("PADDLE_FI_RAISE")
            fault.reset()
        # the parked copy is LOST: slot freed, nothing in the lot
        assert not vrep.engine._parked
        assert vrep.engine.pool.used == 0
        vrep.kill()                        # the driver thread would die
        clock[0] += 2.0                    # heartbeat goes stale
        assert router.check_health() == [victim]
        assert router._table[gid].resubmits == 1
        other = router.replicas[router._table[gid].replica]
        assert other is not vrep
        done = False
        while not done:
            assert time.monotonic() < deadline
            other.pump()
            new, done, state = router.harvest(gid)
            got += new
        assert got == want                 # no double delivery, no gap
        assert state == "finished"
        assert router.failovers_total == 1
        serving_metrics_ok(other.engine)


# =====================================================================
# gateway tenant admission (host units — wire pins live in
# tools/check_http_surface.py)
# =====================================================================
class TestGatewayQos:
    def test_tenant_bucket_rate_limit(self):
        gw = Gateway(None, port=0, tenant_rate=0.5, tenant_burst=2,
                     tenant_quota=0)
        assert gw._tenant_admit(None) is None      # untagged bypasses
        assert gw._tenant_admit("t1") is None      # burst token 1
        assert gw._tenant_admit("t1") is None      # burst token 2
        code, retry = gw._tenant_admit("t1")       # bucket empty
        assert code == "rate_limited"
        # Retry-After from THIS tenant's refill: ~ceil(1/0.5), clamped
        assert 1 <= retry <= 30 and retry >= 2
        assert gw._tenant_admit("t2") is None      # tenant isolation

    def test_tenant_quota_and_release(self):
        from paddle_tpu.serving_cluster import protocol as P
        gw = Gateway(None, port=0, tenant_rate=0, tenant_quota=1)
        assert gw._tenant_admit("t") is None
        code, retry = gw._tenant_admit("t")
        assert code == "quota_exceeded"
        # no refill configured -> the protocol floor, not an invention
        assert retry == P.RETRY_AFTER_S
        gw._tenant_release("t")
        assert gw._tenant_admit("t") is None       # quota freed
        gw._tenant_release("t")
        gw._tenant_release("ghost")                # never goes negative
        assert gw._tenant_live == {}

    def test_config_validation(self):
        with pytest.raises(ValueError):
            Gateway(None, port=0, tenant_burst=0)
        with pytest.raises(ValueError):
            Gateway(None, port=0, tenant_rate=-1)

    def test_should_shed_decomposition_gate(self):
        """Shedding is (1) low class only, (2) watermark-gated, and
        (3) only when the PR-11 queue-vs-service split attributes the
        SLO pain to QUEUEING — shedding can't fix slow service."""
        class StubRouter:
            def __init__(self, qm, vq, vs):
                self._p = {"queue_mean": qm, "violated_queue": vq,
                           "violated_service": vs}

            def qos_pressure(self):
                return self._p

        hot = StubRouter(5.0, 3, 1)
        gw = Gateway(hot, port=0, shed_depth=2.0)
        assert gw._should_shed("low") is True
        assert gw._should_shed("normal") is False  # never sheds better
        assert gw._should_shed("high") is False
        # service-dominated pain: shedding would not help -> admit
        gw_svc = Gateway(StubRouter(5.0, 1, 3), port=0, shed_depth=2.0)
        assert gw_svc._should_shed("low") is False
        # below the watermark -> admit
        gw_idle = Gateway(StubRouter(1.0, 3, 1), port=0, shed_depth=2.0)
        assert gw_idle._should_shed("low") is False
        # knob off (the default) -> never shed, no router call at all
        gw_off = Gateway(None, port=0)
        assert gw_off._should_shed("low") is False
