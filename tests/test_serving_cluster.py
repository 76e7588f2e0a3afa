"""Cluster serving front-end: gateway + router + replicas.

Contracts under test:
  * consistent-hash ring: replica add/remove moves only the
    removed/added replica's keys (prefix affinity survives churn);
  * router policies: queue-depth tie-breaking, saturation spill,
    template->replica affinity, idempotent re-submission by request id,
    schema_version trust;
  * the engine's incremental-harvest API: a tracked reader never loses
    a finished request to the bounded results cap (the documented SSE
    race this API closes);
  * e2e over real HTTP: OpenAI-compatible JSON + SSE match the
    sequential FusedDecoder oracle token-for-token, zero retraces per
    replica across router churn, and a replica killed MID-STREAM fails
    over with greedy token parity — all waits bounded;
  * tools/check_http_surface.py passes (the wire protocol is pinned).
"""
import importlib.util
import json
import os
import socket
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference.generation import FusedDecoder
from paddle_tpu.inference.serving import AdmissionFull, ServingEngine
from paddle_tpu.inference.telemetry import SNAPSHOT_SCHEMA_VERSION
from paddle_tpu.nn.layer.common import Embedding, Linear
from paddle_tpu.serving_cluster import (Gateway, HashRing, LocalReplica,
                                        NoReplicaError, Router)
from paddle_tpu.serving_cluster.replica import ReplicaError
from paddle_tpu.testing.oracle import sequential_tokens

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, E, H, FF, L = 97, 32, 4, 64, 2
WAIT_S = 120                              # bound on every drain loop


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


# =====================================================================
# consistent-hash ring
# =====================================================================
class TestHashRing:
    def test_minimal_key_movement_on_remove_and_add(self):
        ring = HashRing()
        for n in ("r0", "r1", "r2", "r3"):
            ring.add(n)
        keys = [f"template-{i}".encode() for i in range(256)]
        before = {k: ring.owner(k) for k in keys}
        ring.remove("r2")
        after = {k: ring.owner(k) for k in keys}
        moved = [k for k in keys if before[k] != after[k]]
        # ONLY keys r2 owned may move, and they all must
        assert all(before[k] == "r2" for k in moved)
        assert all(after[k] != "r2" for k in keys)
        assert moved == [k for k in keys if before[k] == "r2"]
        # re-adding restores the exact previous ownership (hash points
        # are a pure function of the name)
        ring.add("r2")
        assert {k: ring.owner(k) for k in keys} == before
        # balance sanity: every replica owns SOME keys at 256 keys
        from collections import Counter
        counts = Counter(before.values())
        assert set(counts) == {"r0", "r1", "r2", "r3"}

    def test_empty_ring_owner_is_none(self):
        assert HashRing().owner(b"k") is None


# =====================================================================
# router policies over stub replicas (no engines, no devices)
# =====================================================================
class FakeReplica:
    def __init__(self, name, queue_depth=0, slots_free=2, num_slots=2,
                 kv_used=None, schema=SNAPSHOT_SCHEMA_VERSION,
                 prefill_cap=4, full=False):
        self.name = name
        self.engine = None
        self.queue_depth = queue_depth
        self.slots_free = slots_free
        self.num_slots = num_slots
        self.kv_used = kv_used
        self.schema = schema
        self.prefill_cap = prefill_cap
        self.full = full
        self.submitted = []
        self._rid = 0

    def snapshot(self):
        snap = {"schema_version": self.schema, "replica": self.name,
                "queue_depth": self.queue_depth,
                "slots_free": self.slots_free,
                "num_slots": self.num_slots,
                "prefill_cap": self.prefill_cap}
        if self.kv_used is not None:
            snap["kv_blocks"] = {"kv_blocks_total": 16,
                                 "kv_blocks_used": self.kv_used,
                                 "kv_blocks_free": 16 - self.kv_used,
                                 "kv_blocks_used_peak": self.kv_used}
        return snap

    def submit(self, prompt, **kw):
        if self.full:
            raise AdmissionFull(f"{self.name} full")
        self._rid += 1
        self.submitted.append((self._rid, list(prompt), kw))
        return self._rid

    def harvest(self, rid):
        return [], True, "finished"

    def release(self, rid):
        pass

    def heartbeat_age(self):
        return 0.0

    def metrics_prometheus(self):
        return ("# HELP fake_metric a stub sample\n"
                "# TYPE fake_metric gauge\nfake_metric 1\n")

    @property
    def alive(self):
        return True


def _router(reps, **kw):
    kw.setdefault("snap_max_age_s", 0.0)   # stubs: always re-snapshot
    return Router(reps, **kw)


class TestRouterPolicies:
    def test_least_loaded_scores_and_tie_break(self):
        reps = [FakeReplica("a", queue_depth=3, slots_free=0),
                FakeReplica("b", queue_depth=1, slots_free=1),
                FakeReplica("c", queue_depth=1, slots_free=1)]
        r = _router(reps, policy="least_loaded")
        r.submit([1, 2, 3], max_new_tokens=2)
        # b and c tie on score; the name breaks the tie deterministically
        assert reps[1].submitted and not reps[0].submitted
        # pool pressure breaks a queue/slot tie: c's pool is emptier
        reps2 = [FakeReplica("a", queue_depth=0, slots_free=2, kv_used=12),
                 FakeReplica("b", queue_depth=0, slots_free=2, kv_used=2)]
        r2 = _router(reps2, policy="least_loaded")
        r2.submit([1, 2, 3], max_new_tokens=2)
        assert reps2[1].submitted and not reps2[0].submitted

    def test_prefix_affinity_same_template_same_replica(self):
        reps = [FakeReplica(f"r{i}") for i in range(3)]
        r = _router(reps, policy="prefix_affinity")
        t1 = [7, 8, 9, 10, 1]             # >= prefill_cap=4: affine
        t2 = [20, 21, 22, 23, 1]
        for sfx in range(5):
            r.submit(t1[:4] + [sfx], max_new_tokens=2)
            r.submit(t2[:4] + [sfx], max_new_tokens=2)
        homes = {tuple(p[:4]): set() for _, p, _ in
                 [s for rep in reps for s in rep.submitted]}
        for rep in reps:
            for _, p, _ in rep.submitted:
                homes[tuple(p[:4])].add(rep.name)
        # every template lives on exactly ONE replica
        assert all(len(v) == 1 for v in homes.values()), homes

    def test_prefix_affinity_short_prompt_falls_back_to_load(self):
        reps = [FakeReplica("a", queue_depth=5), FakeReplica("b")]
        r = _router(reps, policy="prefix_affinity")
        r.submit([1, 2, 3], max_new_tokens=2)   # < prefill_cap: no block
        assert reps[1].submitted and not reps[0].submitted

    def test_prefix_affinity_saturation_spill(self):
        reps = [FakeReplica("r0"), FakeReplica("r1")]
        r = _router(reps, policy="prefix_affinity", spill_depth=4)
        template = [5, 6, 7, 8, 9]
        r.submit(template, max_new_tokens=2)
        owner = next(rep for rep in reps if rep.submitted)
        other = next(rep for rep in reps if not rep.submitted)
        # saturate the owner past spill_depth: the SAME template must
        # spill to the least-loaded replica instead of queueing forever
        owner.queue_depth = 4
        r.submit(template, max_new_tokens=2)
        assert other.submitted, "saturated owner did not spill"
        # drain the owner: affinity resumes (the spill is pressure-
        # scoped, not a permanent re-home)
        owner.queue_depth = 0
        n_owner = len(owner.submitted)
        r.submit(template, max_new_tokens=2)
        assert len(owner.submitted) == n_owner + 1

    def test_admission_full_spills_then_propagates(self):
        a, b = FakeReplica("a", full=True), FakeReplica("b")
        r = _router([a, b], policy="least_loaded")
        r.submit([1, 2, 3], max_new_tokens=2)   # a sheds -> spills to b
        assert b.submitted
        b.full = True
        with pytest.raises(AdmissionFull):
            r.submit([1, 2, 3], max_new_tokens=2)

    def test_idempotent_by_request_id(self):
        a = FakeReplica("a")
        r = _router([a], policy="least_loaded")
        g1 = r.submit([1, 2, 3], request_id="client-1", max_new_tokens=2)
        g2 = r.submit([1, 2, 3], request_id="client-1", max_new_tokens=2)
        assert g1 == g2 and len(a.submitted) == 1

    def test_schema_version_mismatch_refused(self):
        ok = FakeReplica("ok")
        drift = FakeReplica("drift", schema=SNAPSHOT_SCHEMA_VERSION + 1)
        r = _router([drift, ok], policy="least_loaded")
        r.refresh(force=True)
        assert r.version_mismatches >= 1
        # the drifted replica is unscored (= worst score): traffic goes
        # to the replica whose payload the router can trust
        r.submit([1, 2, 3], max_new_tokens=2)
        assert ok.submitted and not drift.submitted

    def test_no_alive_replica_raises(self):
        a = FakeReplica("a")
        r = _router([a], policy="least_loaded")
        r.mark_dead("a")
        with pytest.raises(NoReplicaError):
            r.submit([1, 2, 3], max_new_tokens=2)

    def test_failover_resubmits_with_remaining_deadline(self):
        """A deadline_s request fails over with its REMAINING budget
        (measured from the original submit), and an already-expired
        one goes straight to state 'expired' instead of restarting its
        clock on the new engine."""
        clock = [0.0]
        # b reports heavy load, so least_loaded pins both requests on a
        a = FakeReplica("a")
        b = FakeReplica("b", queue_depth=50)
        r = _router([a, b], policy="least_loaded",
                    clock=lambda: clock[0])
        g1 = r.submit([1, 2, 3], max_new_tokens=4, deadline_s=10.0)
        g2 = r.submit([4, 5, 6], max_new_tokens=4, deadline_s=1.0)
        assert r.poll(g1)["replica"] == r.poll(g2)["replica"] == "a"
        clock[0] = 3.0                     # g2's 1.0s budget is gone
        r.mark_dead("a")
        p2 = r.poll(g2)
        assert p2["done"] and p2["state"] == "expired"
        assert p2["resubmits"] == 0
        p1 = r.poll(g1)
        assert p1["resubmits"] == 1 and p1["replica"] == "b"
        kw = b.submitted[-1][2]
        assert kw["deadline_s"] == pytest.approx(7.0)

    def test_concurrent_readers_each_see_full_stream(self):
        """harvest(gid, cursor): the assignment keeps the full token
        history, so two readers of ONE gid (an idempotent client
        retry) each stream everything — the old shared destructive
        cursor split the tokens between them."""

        class Scripted(FakeReplica):
            def __init__(self, name, script):
                super().__init__(name)
                self.script = list(script)

            def harvest(self, rid):
                if self.script:
                    return self.script.pop(0), not self.script, \
                        ("finished" if not self.script else "running")
                return [], True, "finished"

        rep = Scripted("s", [[1, 2], [3], [4, 5]])
        r = _router([rep], policy="least_loaded")
        gid = r.submit([7, 8, 9], request_id="dup", max_new_tokens=5)
        assert r.submit([7, 8, 9], request_id="dup",
                        max_new_tokens=5) == gid
        c1 = c2 = 0
        s1, s2 = [], []
        done = False
        while not done:
            new, done, _ = r.harvest(gid, c1)
            s1 += new
            c1 += len(new)
        new, d2, _ = r.harvest(gid, c2)    # reader 2 starts late
        s2 += new
        assert d2 and s1 == s2 == [1, 2, 3, 4, 5]


# =====================================================================
# router decision audit (the placement explainability surface)
# =====================================================================
class TestRouterAudit:
    def test_reason_coverage_and_counters(self):
        from paddle_tpu.serving_cluster import AUDIT_REASONS
        reps = [FakeReplica("r0"), FakeReplica("r1")]
        r = _router(reps, policy="prefix_affinity", spill_depth=4)
        template = [5, 6, 7, 8, 9]
        r.submit(template, max_new_tokens=2)        # affinity_hit
        assert r.audit[-1]["reason"] == "affinity_hit"
        owner_name = r.audit[-1]["chosen"]
        r.submit([1, 2, 3], max_new_tokens=2)       # short: least_loaded
        assert r.audit[-1]["reason"] == "least_loaded"
        owner = next(rep for rep in reps if rep.name == owner_name)
        owner.queue_depth = 4                       # saturate the owner
        r.submit(template, max_new_tokens=2)        # -> spill
        assert r.audit[-1]["reason"] == "spill"
        owner.queue_depth = 0
        owner.full = True                           # shedding owner
        r.submit(template, max_new_tokens=2)        # -> spill (retry)
        assert r.audit[-1]["reason"] == "spill"
        owner.full = False
        # failover: kill the replica holding a live assignment
        gid = r.submit(template, max_new_tokens=2, trace_id="aud-1")
        held_by = r.poll(gid)["replica"]
        r.mark_dead(held_by)
        assert r.audit[-1]["reason"] == "failover"
        assert r.audit[-1]["trace_id"] == "aud-1"
        assert r.audit[-1]["attempt"] == 2
        # orphaned: the survivor dies too, draining onto nothing
        survivor = next(n for n in r.alive_names())
        r.submit(template, max_new_tokens=2)
        r.mark_dead(survivor)
        assert any(e["reason"] == "orphaned" and e["chosen"] is None
                   for e in r.audit)
        # counters reconcile with the ring's full history (the ring
        # here is unbounded enough to hold everything)
        assert sum(r.audit_counts.values()) == len(r.audit)
        assert set(r.audit_counts) == set(AUDIT_REASONS)
        # every entry is JSON-able (the cluster trace consumes it)
        json.dumps(list(r.audit))
        # round_robin policy stamps its own reason
        rr = _router([FakeReplica("a"), FakeReplica("b")],
                     policy="round_robin")
        rr.submit([1, 2, 3], max_new_tokens=2)
        assert rr.audit[-1]["reason"] == "round_robin"
        # ... and the exposition carries the per-reason counters
        text = rr.metrics_prometheus()
        assert ('paddle_gateway_route_decisions_total'
                '{reason="round_robin"} 1') in text
        assert ('paddle_gateway_route_decisions_total'
                '{reason="failover"} 0') in text

    def test_audit_ring_bounded(self):
        reps = [FakeReplica("a"), FakeReplica("b")]
        r = _router(reps, policy="least_loaded", audit_ring=4)
        for i in range(10):
            r.submit([1, 2, i], max_new_tokens=2)
        assert len(r.audit) == 4                    # bounded
        assert r.audit_counts["least_loaded"] == 10  # counters keep all
        # the ring holds the MOST RECENT decisions
        assert [e["gid"] for e in r.audit] == \
            [f"req-{i}" for i in range(7, 11)]

    def test_audit_ring_zero_disables_entries_not_counters(self):
        # PADDLE_ROUTER_AUDIT_RING=0 turns the ring off entirely, but
        # the per-reason counters (pinned in /metrics) keep counting
        r = _router([FakeReplica("a"), FakeReplica("b")],
                    policy="least_loaded", audit_ring=0)
        for i in range(5):
            r.submit([1, 2, i], max_new_tokens=2)
        assert len(r.audit) == 0
        assert r.audit_counts["least_loaded"] == 5

    def test_idempotent_repeat_keeps_original_trace_id(self):
        # a retry with the same request_id but a fresh proxy-minted
        # trace id must resolve to the ORIGINAL submission's trace id
        # — that is the id the engine spans and the audit carry
        r = _router([FakeReplica("a"), FakeReplica("b")],
                    policy="least_loaded")
        gid = r.submit([1, 2, 3], max_new_tokens=2,
                       request_id="ridem", trace_id="trace-orig")
        gid2 = r.submit([1, 2, 3], max_new_tokens=2,
                        request_id="ridem", trace_id="trace-retry")
        assert gid2 == gid
        assert r.trace_id_of(gid) == "trace-orig"
        r.release(gid)
        assert r.trace_id_of(gid) is None


# =====================================================================
# engine incremental harvest (the SSE primitive)
# =====================================================================
class TestEngineHarvest:
    def test_tracked_reader_survives_results_cap(self):
        """The documented race this API closes: telemetry_ring=2 caps
        results at 2, but 5 TRACKED requests all stream their full
        outputs to an arbitrarily slow reader."""
        fmt, embed, head = _model()
        eng = _engine(fmt, embed, head, telemetry_ring=2)
        rng = np.random.RandomState(0)
        rids = [eng.submit(rng.randint(1, V, (5,)).astype(np.int32),
                           max_new_tokens=4) for _ in range(5)]
        for rid in rids:
            eng.track(rid)
        eng.run()                          # everything finishes FIRST
        assert len(eng.results) == 2       # the cap did its job
        for rid in rids:                   # ... and nobody lost tokens
            toks, done, state = eng.harvest_new_tokens(rid)
            assert done and state == "finished" and len(toks) == 4
        assert not eng._req_index and not eng._harvest

    def test_incremental_monotone_and_poll(self):
        fmt, embed, head = _model()
        eng = _engine(fmt, embed, head)
        rid = eng.submit(np.arange(1, 9, dtype=np.int32),
                         max_new_tokens=6)
        eng.track(rid)
        assert eng.poll(rid)["state"] == "queued"
        got = []
        deadline = time.monotonic() + WAIT_S
        done = False
        while not done:
            assert time.monotonic() < deadline
            eng.step()
            new, done, state = eng.harvest_new_tokens(rid)
            got.extend(new)
        assert got == [int(t) for t in eng.results[rid]["tokens"]]
        assert eng.poll(rid)["n_tokens"] == 6
        # the cursor is gone: a re-harvest is the unknown-rid error...
        # unless the results dict still holds it (it does here)
        new, done, _ = eng.harvest_new_tokens(rid)
        assert done and new == got         # fresh cursor, full replay

    def test_untracked_evicted_request_raises(self):
        fmt, embed, head = _model()
        eng = _engine(fmt, embed, head, telemetry_ring=2)
        rng = np.random.RandomState(1)
        rids = [eng.submit(rng.randint(1, V, (5,)).astype(np.int32),
                           max_new_tokens=3) for _ in range(4)]
        eng.run()
        assert rids[0] not in eng.results  # evicted by the cap
        with pytest.raises(KeyError):
            eng.harvest_new_tokens(rids[0])


# =====================================================================
# e2e: gateway over >= 2 replicas, real HTTP
# =====================================================================
def _post(port, body, timeout=WAIT_S):
    import http.client
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("POST", "/v1/completions", json.dumps(body))
    r = c.getresponse()
    data = r.read()
    c.close()
    return r.status, data


def _sse_collect(port, body, timeout=WAIT_S):
    payload = json.dumps(body).encode()
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    s.sendall(b"POST /v1/completions HTTP/1.1\r\nHost: x\r\n"
              b"Content-Length: %d\r\n\r\n%s" % (len(payload), payload))
    buf = b""
    while True:
        chunk = s.recv(65536)
        if not chunk:
            break
        buf += chunk
    s.close()
    toks, reason = [], None
    for ln in buf.partition(b"\r\n\r\n")[2].split(b"\n"):
        ln = ln.strip()
        if not ln.startswith(b"data: ") or ln == b"data: [DONE]":
            continue
        ch = json.loads(ln[6:])["choices"][0]
        toks += ch["tokens"]
        reason = ch["finish_reason"] or reason
    return toks, reason


class TestClusterE2E:
    def test_gateway_completions_match_oracle_json_and_sse(self):
        """Two replicas behind one endpoint: JSON and SSE both produce
        exactly the sequential-decoder tokens — routing is invisible."""
        fmt, embed, head = _model()
        reps = [LocalReplica(f"replica{i}", _engine(fmt, embed, head))
                for i in range(2)]
        gw = Gateway(Router(reps, policy="round_robin",
                            snap_max_age_s=0.0),
                     port=0, hb_s=0.1).start_background()
        try:
            rng = np.random.RandomState(0)
            for _ in range(3):
                prompt = [int(t) for t in rng.randint(1, V, (10,))]
                want = _oracle(fmt, embed, head, prompt, 6)
                st, data = _post(gw.port, {"prompt": prompt,
                                           "max_tokens": 6})
                obj = json.loads(data)
                assert st == 200 and obj["choices"][0]["tokens"] == want
                toks, reason = _sse_collect(
                    gw.port, {"prompt": prompt, "max_tokens": 6,
                              "stream": True})
                assert toks == want and reason == "length"
        finally:
            gw.stop()
            for r in reps:
                r.close()

    def test_zero_retraces_across_router_churn(self):
        """The router is pure host code: after each replica compiled
        its executables once, cluster churn must not trace anything new
        on ANY replica."""
        fmt, embed, head = _model()
        reps = [LocalReplica(f"replica{i}", _engine(fmt, embed, head),
                             threaded=False)
                for i in range(2)]
        router = Router(reps, policy="round_robin", snap_max_age_s=0.0)
        rng = np.random.RandomState(7)

        def drive(n):
            gids = [router.submit(
                [int(t) for t in rng.randint(1, V, (12,))],
                max_new_tokens=5) for _ in range(n)]
            deadline = time.monotonic() + WAIT_S
            done = set()
            while len(done) < len(gids):
                assert time.monotonic() < deadline
                for r in reps:
                    r.pump()
                for g in gids:
                    if g not in done and router.harvest(g)[1]:
                        done.add(g)

        drive(4)                           # warmup: compile everything
        traces = [r.engine.metrics()["traces"] for r in reps]
        drive(8)                           # churn through both replicas
        assert [r.engine.metrics()["traces"] for r in reps] == traces

    def test_kill_replica_mid_stream_token_identical(self):
        """THE failover contract: a replica killed mid-request (step
        hook fires at exactly step 4, while the request is in flight)
        is detected, its stream re-routed, and the client sees the
        byte-identical greedy token sequence with no duplicates."""
        fmt, embed, head = _model()
        hits = {"n": 0}

        def killer(rep):
            hits["n"] += 1
            if hits["n"] == 4:
                rep.kill()

        reps = [LocalReplica(f"replica{i}", _engine(fmt, embed, head),
                             step_hook=killer)
                for i in range(2)]
        router = Router(reps, policy="round_robin", hb_dead_s=0.3,
                        snap_max_age_s=0.0)
        gw = Gateway(router, port=0, hb_s=0.05,
                     poll_s=0.002).start_background()
        try:
            prompt = [int(t) for t in
                      np.random.RandomState(0).randint(1, V, (12,))]
            want = _oracle(fmt, embed, head, prompt, 60)
            toks, reason = _sse_collect(
                gw.port, {"prompt": prompt, "max_tokens": 60,
                          "stream": True})
            assert toks == want, (len(toks), len(want))
            assert reason == "length"
            assert router.failovers_total == 1
            assert len(router.dead) == 1
        finally:
            gw.stop()
            for r in reps:
                r.close()

    def test_failover_deterministic_virtual_clock(self):
        """The same drain->re-submit path with NO real time: unthreaded
        replicas, injected clock, explicit health sweeps — kill the
        owner after 3 harvested tokens, advance the clock past the
        heartbeat threshold, and the request finishes elsewhere with
        exact token parity and exactly-once delivery."""
        fmt, embed, head = _model()
        clock = [0.0]
        reps = [LocalReplica(f"replica{i}", _engine(fmt, embed, head),
                             threaded=False, clock=lambda: clock[0])
                for i in range(2)]
        router = Router(reps, policy="round_robin", hb_dead_s=1.0,
                        snap_max_age_s=0.0, clock=lambda: clock[0])
        prompt = [int(t) for t in
                  np.random.RandomState(3).randint(1, V, (10,))]
        want = _oracle(fmt, embed, head, prompt, 20)
        gid = router.submit(prompt, max_new_tokens=20)
        victim = router._table[gid].replica
        vrep = router.replicas[victim]
        got = []
        deadline = time.monotonic() + WAIT_S
        while len(got) < 3:
            assert time.monotonic() < deadline
            vrep.pump()
            got += router.harvest(gid)[0]
        vrep.kill()
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
        assert got == want                 # identical, no dup, no gap
        assert state == "finished"
        assert router.failovers_total == 1

    def test_trace_id_survives_failover_virtual_clock(self):
        """THE trace-context contract, deterministically: one trace id
        threads submit -> victim replica (attempt 1) -> failover ->
        replacement replica (attempt 2), with token parity — the
        engines' request spans join on the id across the kill."""
        fmt, embed, head = _model()
        clock = [0.0]
        reps = [LocalReplica(f"replica{i}", _engine(fmt, embed, head),
                             threaded=False, clock=lambda: clock[0])
                for i in range(2)]
        router = Router(reps, policy="round_robin", hb_dead_s=1.0,
                        snap_max_age_s=0.0, clock=lambda: clock[0])
        prompt = [int(t) for t in
                  np.random.RandomState(3).randint(1, V, (10,))]
        want = _oracle(fmt, embed, head, prompt, 20)
        gid = router.submit(prompt, max_new_tokens=20,
                            trace_id="trace-failover-1")
        assert router.poll(gid)["trace_id"] == "trace-failover-1"
        assert router.poll(gid)["attempt"] == 1
        victim = router._table[gid].replica
        vrep = router.replicas[victim]
        got = []
        deadline = time.monotonic() + WAIT_S
        while len(got) < 3:
            assert time.monotonic() < deadline
            vrep.pump()
            got += router.harvest(gid)[0]
        # the victim engine's live span carries the trace id, attempt 1
        vspan = next(sp for sp in vrep.engine.telemetry._live.values()
                     if sp.trace_id == "trace-failover-1")
        assert vspan.attempt == 1
        vrep.kill()
        clock[0] += 2.0
        assert router.check_health() == [victim]
        assert router.poll(gid)["attempt"] == 2
        other = router.replicas[router._table[gid].replica]
        done = False
        while not done:
            assert time.monotonic() < deadline
            other.pump()
            new, done, _ = router.harvest(gid)
            got += new
        assert got == want
        # the replacement engine's span: SAME trace id, attempt 2
        dump = other.trace_dump()
        span = next(s for s in dump["spans"]
                    if s["trace_id"] == "trace-failover-1")
        assert span["attempt"] == 2 and span["state"] == "finished"
        # the victim's post-mortem dump still shows attempt 1
        vdump = vrep.trace_dump()
        vs = next(s for s in vdump["spans"]
                  if s["trace_id"] == "trace-failover-1")
        assert vs["attempt"] == 1 and vs["state"] != "finished"

    def test_cluster_trace_merged_export(self, tmp_path):
        """The acceptance gate: a kill-mid-stream drill exports ONE
        merged Perfetto trace that validates and contains, for a
        single trace id, the gateway HTTP span, a router decision,
        and engine request spans on TWO replica pids at attempts 1
        and 2 — with zero retraces per replica and greedy parity."""
        from paddle_tpu.inference.telemetry import validate_chrome_trace
        from paddle_tpu.serving_cluster import export_cluster_trace
        fmt, embed, head = _model()
        hits = {"n": 0}

        def killer(rep):
            hits["n"] += 1
            if hits["n"] == 4:
                rep.kill()

        reps = [LocalReplica(f"replica{i}", _engine(fmt, embed, head),
                             step_hook=killer)
                for i in range(2)]
        router = Router(reps, policy="round_robin", hb_dead_s=0.3,
                        snap_max_age_s=0.0)
        gw = Gateway(router, port=0, hb_s=0.05,
                     poll_s=0.002).start_background()
        try:
            prompt = [int(t) for t in
                      np.random.RandomState(0).randint(1, V, (12,))]
            want = _oracle(fmt, embed, head, prompt, 60)
            payload = json.dumps({"prompt": prompt, "max_tokens": 60,
                                  "stream": True}).encode()
            s = socket.create_connection(("127.0.0.1", gw.port),
                                         timeout=WAIT_S)
            s.sendall(b"POST /v1/completions HTTP/1.1\r\nHost: x\r\n"
                      b"X-Request-Id: trace-drill-1\r\n"
                      b"Content-Length: %d\r\n\r\n%s"
                      % (len(payload), payload))
            buf = b""
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                buf += chunk
            s.close()
            toks = []
            for ln in buf.partition(b"\r\n\r\n")[2].split(b"\n"):
                ln = ln.strip()
                if not ln.startswith(b"data: ") or ln == b"data: [DONE]":
                    continue
                toks += json.loads(ln[6:])["choices"][0]["tokens"]
            assert toks == want               # greedy parity through kill
            assert router.failovers_total == 1

            path = str(tmp_path / "cluster_trace.json")
            export_cluster_trace(gw, path)
            doc = validate_chrome_trace(path)
            evs = doc["traceEvents"]
            tid = "trace-drill-1"
            http_spans = [e for e in evs
                          if e.get("pid") == 0 and e.get("ph") == "X"
                          and (e.get("args") or {}).get("trace_id") == tid
                          and e["name"].startswith("POST")]
            decisions = [e for e in evs
                         if e.get("pid") == 0 and e.get("ph") == "X"
                         and str(e["name"]).startswith("decision")
                         and e["args"].get("trace_id") == tid]
            rep_spans = [e for e in evs
                         if e.get("pid", 0) > 0 and e.get("ph") == "X"
                         and (e.get("args") or {}).get("trace_id") == tid]
            assert http_spans, "gateway HTTP span missing"
            assert decisions, "router decision event missing"
            attempts = sorted(e["args"]["attempt"] for e in rep_spans)
            pids = {e["pid"] for e in rep_spans}
            assert attempts[0] == 1 and attempts[-1] == 2, attempts
            assert len(pids) == 2, "failover did not span two replicas"
            assert {e["args"]["reason"] for e in decisions} >= \
                {"failover"}
            # every event ts is non-negative (the anchor rebase holds)
            assert all(e.get("ts", 0) >= 0 for e in evs)
        finally:
            gw.stop()
            for r in reps:
                r.close()

    def test_orphaned_when_no_replica_left(self):
        fmt, embed, head = _model()
        rep = LocalReplica("only", _engine(fmt, embed, head),
                           threaded=False)
        router = Router([rep], policy="round_robin", snap_max_age_s=0.0)
        gid = router.submit([1, 2, 3, 4, 5], max_new_tokens=8)
        rep.kill()
        router.mark_dead("only")
        assert router._table[gid].orphaned
        with pytest.raises(NoReplicaError):
            router.harvest(gid)


# =====================================================================
# disaggregated prefill/decode serving (role-specialized replicas)
# =====================================================================
def _drive_cluster(router, reps, gids):
    """Pump every unthreaded replica and harvest every stream until all
    finish (bounded). Returns {gid: [tokens]}."""
    outs = {g: [] for g in gids}
    done = {g: False for g in gids}
    deadline = time.monotonic() + WAIT_S
    while not all(done.values()):
        assert time.monotonic() < deadline, "disagg drive stalled"
        for r in reps:
            r.pump()
        for g in gids:
            if not done[g]:
                new, d, _ = router.harvest(g, len(outs[g]))
                outs[g].extend(new)
                done[g] = d
    return outs


class TestDisaggServing:
    """Role-split cluster (prefill workers hold prompt-complete
    sessions; the router ships their KV to decode workers) vs the SAME
    arrivals on a mixed single-engine baseline: token parity, zero
    prompt recompute, streamed mid-prefill handoff, backpressure
    bounce-back on a tight decode pool, zero retraces after warmup."""

    def _prompts(self, seed, n):
        rng = np.random.RandomState(seed)
        return [[int(t) for t in rng.randint(1, V, (int(ln),))]
                for ln in rng.randint(6, 15, (n,))]

    def _mixed_baseline(self, fmt, embed, head, prompts, max_new=6,
                        **ekw):
        eng = _engine(fmt, embed, head, num_slots=4,
                      prefix_cache_blocks=32, **ekw)
        rep = LocalReplica("m0", eng, threaded=False)
        rt = Router([rep], snap_max_age_s=0.0)
        paddle.seed(1234)                 # per-request sampler seeds
        gids = [rt.submit(p, max_new_tokens=max_new) for p in prompts]
        outs = _drive_cluster(rt, [rep], gids)
        return eng, [outs[g] for g in gids]

    def _disagg_cluster(self, fmt, embed, head, handoff_blocks=None,
                        dc_kw=None, **ekw):
        eng_p = _engine(fmt, embed, head, role="prefill", num_slots=2,
                        prefix_cache_blocks=32, **ekw)
        dkw = dict(num_slots=4, prefix_cache_blocks=32, **ekw)
        dkw.update(dc_kw or {})
        eng_d = _engine(fmt, embed, head, role="decode", **dkw)
        reps = [LocalReplica("pf0", eng_p, threaded=False),
                LocalReplica("dc0", eng_d, threaded=False)]
        rt = Router(reps, snap_max_age_s=0.0,
                    handoff_blocks=handoff_blocks)
        return eng_p, eng_d, reps, rt

    def test_greedy_parity_and_zero_recompute(self):
        fmt, embed, head = _model()
        prompts = self._prompts(21, 6)
        eng_m, want = self._mixed_baseline(fmt, embed, head, prompts)
        eng_p, eng_d, reps, rt = self._disagg_cluster(fmt, embed, head)
        paddle.seed(1234)
        gids = [rt.submit(p, max_new_tokens=6) for p in prompts]
        outs = _drive_cluster(rt, reps, gids)
        assert [outs[g] for g in gids] == want
        # every session prefilled on pf0, decoded on dc0 — one handoff
        # each, no failover/replay anywhere
        assert rt.handoffs_total == len(prompts)
        assert rt.failovers_total == 0
        assert rt.migration_aborts_total == 0
        # ZERO prompt recompute: the decode engine never ran a prefill
        # (its sessions all arrived prompt-complete over the KV wire),
        # and the prefill side computed exactly what the mixed
        # baseline did for the same arrivals
        mp, md = eng_p.metrics(), eng_d.metrics()
        assert md["prefill_tokens_computed"] == 0
        assert mp["prefill_tokens_computed"] == \
            eng_m.metrics()["prefill_tokens_computed"]
        # the transfer counters reconcile across the wire
        assert mp["kv_blocks_shipped"] == md["kv_blocks_adopted"] > 0

    def test_sampled_parity_across_handoff(self):
        """Sampler state (per-request seed + counter) rides the export:
        a sampled stream is identical whether it decodes in place or on
        the other side of a KV handoff."""
        fmt, embed, head = _model()
        prompts = self._prompts(22, 4)
        samp = dict(do_sample=True, top_k=12, top_p=0.9,
                    temperature=0.8)
        eng_m, want = self._mixed_baseline(fmt, embed, head, prompts,
                                           **samp)
        eng_p, eng_d, reps, rt = self._disagg_cluster(fmt, embed, head,
                                                      **samp)
        paddle.seed(1234)                 # same seed draw order
        gids = [rt.submit(p, max_new_tokens=6) for p in prompts]
        outs = _drive_cluster(rt, reps, gids)
        assert [outs[g] for g in gids] == want
        assert rt.handoffs_total == len(prompts)
        assert eng_d.metrics()["prefill_tokens_computed"] == 0

    def test_streamed_handoff_ships_mid_prefill(self):
        """handoff_blocks=1: committed prompt blocks stream to the
        decode target WHILE the prefill tail is still running — the
        shipped counter moves before the request produces a token."""
        fmt, embed, head = _model()
        long_prompt = [int(t) for t in
                       np.random.RandomState(9).randint(1, V, (40,))]
        eng_m, want = self._mixed_baseline(fmt, embed, head,
                                           [long_prompt], max_new=6)
        eng_p, eng_d, reps, rt = self._disagg_cluster(
            fmt, embed, head, handoff_blocks=1)
        gid = rt.submit(long_prompt, max_new_tokens=6)
        got, shipped_mid = [], 0
        deadline = time.monotonic() + WAIT_S
        done = False
        while not done:
            assert time.monotonic() < deadline
            for r in reps:
                r.pump()
            new, done, _ = rt.harvest(gid, len(got))
            got.extend(new)
            if not got and not done:
                # still prefilling (prefill_cap=8 chunks a 40-token
                # prompt): record the transfer progress so far
                shipped_mid = max(shipped_mid,
                                  eng_p.metrics()["kv_blocks_shipped"])
        assert shipped_mid > 0, \
            "no KV block left the prefill worker before the first token"
        assert got == want[0]
        assert rt.handoffs_total == 1 and rt.failovers_total == 0
        assert eng_d.metrics()["prefill_tokens_computed"] == 0
        # staged prefix + final handoff moved every block exactly once
        assert eng_p.metrics()["kv_blocks_shipped"] == \
            eng_d.metrics()["kv_blocks_adopted"]

    def test_tight_decode_pool_backpressure_then_parity(self):
        """A decode pool too small for the offered load: handoffs
        bounce back ('held' = backpressure, not failure) and retry as
        sessions retire — everything still finishes with exact parity
        and zero drops/replays."""
        fmt, embed, head = _model()
        prompts = self._prompts(23, 6)
        eng_m, want = self._mixed_baseline(fmt, embed, head, prompts)
        # decode: 2 slots, pool sized to ~2 resident sessions
        eng_p, eng_d, reps, rt = self._disagg_cluster(
            fmt, embed, head, dc_kw=dict(num_slots=2,
                                         prefix_cache_blocks=8))
        paddle.seed(1234)
        gids = [rt.submit(p, max_new_tokens=6) for p in prompts]
        outs = _drive_cluster(rt, reps, gids)
        assert [outs[g] for g in gids] == want
        assert rt.handoffs_total == len(prompts)
        assert rt.failovers_total == 0
        assert eng_d.metrics()["prefill_tokens_computed"] == 0

    def test_zero_retraces_after_warmup_both_roles(self):
        """After one warmup wave compiled both roles' executables
        (prefill chunks + export on pf0, import + decode on dc0),
        steady-state disagg traffic traces NOTHING new on either."""
        fmt, embed, head = _model()
        eng_p, eng_d, reps, rt = self._disagg_cluster(fmt, embed, head)
        rng = np.random.RandomState(31)

        def wave(n):
            gids = [rt.submit([int(t) for t in rng.randint(1, V, (10,))],
                              max_new_tokens=5) for _ in range(n)]
            _drive_cluster(rt, reps, gids)

        wave(3)                            # warmup: compile everything
        traces = [eng_p.metrics()["traces"], eng_d.metrics()["traces"]]
        wave(6)
        assert [eng_p.metrics()["traces"],
                eng_d.metrics()["traces"]] == traces
        assert rt.handoffs_total == 9

    def test_prefill_drain_routes_by_remaining_work(self):
        """THE drain-role contract: draining a PREFILL replica sends a
        session that still owes prefill work to another prefill-capable
        replica (a decode-only target would starve it), while a
        prompt-complete held session drains to the decode pool."""
        fmt, embed, head = _model()
        kw = dict(prefix_cache_blocks=32)
        reps = [LocalReplica("pf0", _engine(fmt, embed, head,
                                            role="prefill", **kw),
                             threaded=False),
                LocalReplica("pf1", _engine(fmt, embed, head,
                                            role="prefill", **kw),
                             threaded=False),
                LocalReplica("dc0", _engine(fmt, embed, head,
                                            role="decode", **kw),
                             threaded=False)]
        rt = Router(reps, snap_max_age_s=0.0)
        prompt = [int(t) for t in
                  np.random.RandomState(4).randint(1, V, (12,))]
        want = _oracle(fmt, embed, head, prompt, 8)
        gid = rt.submit(prompt, max_new_tokens=8)
        first = rt._table[gid].replica
        assert first in ("pf0", "pf1")     # placement is role-aware too
        # (a) un-prefilled (queued) session: drain must land it on the
        # OTHER prefill replica, never the decode-only one
        summary = rt.remove_replica(first, migrate=True)
        assert summary["migrated"] == 1
        second = rt._table[gid].replica
        assert second == ({"pf0", "pf1"} - {first}).pop()
        # (b) run the prompt to completion on the prefill engine: it
        # HOLDS the session; draining now must land it decode-side
        srep = rt.replicas[second]
        deadline = time.monotonic() + WAIT_S
        while srep.engine.has_work:
            assert time.monotonic() < deadline
            srep.pump()
        summary = rt.remove_replica(second, migrate=True)
        assert summary["migrated"] == 1
        assert rt._table[gid].replica == "dc0"
        got, done = [], False
        while not done:
            assert time.monotonic() < deadline
            reps[2].pump()
            new, done, state = rt.harvest(gid, len(got))
            got.extend(new)
        assert got == want and state == "finished"
        assert rt.failovers_total == 0     # drains, not replays


# =====================================================================
# role-aware autoscaler: per-pool watermarks
# =====================================================================
class TestRoleAutoscaler:
    def _scaler(self, router=None, spawn=None, **kw):
        from paddle_tpu.serving_cluster.autoscale import Autoscaler
        kw.setdefault("role_aware", True)
        kw.setdefault("pf_queue_high", 4.0)
        kw.setdefault("pf_queue_low", 1.0)
        kw.setdefault("dc_kv_free_low", 0.2)
        kw.setdefault("dc_sessions_high", 0.8)
        kw.setdefault("dc_sessions_low", 0.3)
        kw.setdefault("max_replicas", 8)
        return Autoscaler(router if router is not None else Router([]),
                          spawn or (lambda *a: None), **kw)

    def test_decide_roles_truth_table(self):
        """The per-pool watermark logic, pinned case by case: the two
        pools scale on DIFFERENT signal families, scale-up beats
        scale-down, prefill backlog beats decode pressure, and a pool
        with no snapshots contributes no verdict."""
        a = self._scaler()

        def sig(pq=2.0, kv=0.5, sess=0.5, npf=1, ndc=1):
            return {"prefill_replicas": npf, "decode_replicas": ndc,
                    "prefill_snapshots": npf, "decode_snapshots": ndc,
                    "prefill_queue_mean": pq,
                    "decode_kv_free_frac": kv,
                    "decode_sessions_frac": sess}

        cases = [
            (sig(), None),                            # mid-band: hold
            (sig(pq=5.0), ("up", "prefill")),         # prompt backlog
            (sig(kv=0.1), ("up", "decode")),          # kv starvation
            (sig(sess=0.9), ("up", "decode")),        # slots resident
            # both pools want up: the user-visible TTFT backlog wins
            (sig(pq=5.0, kv=0.1), ("up", "prefill")),
            (sig(pq=0.5), ("down", "prefill")),       # idle prefill
            (sig(sess=0.2), ("down", "decode")),      # idle decode
            # decode-down needs BOTH idle sessions and kv headroom
            (sig(sess=0.2, kv=0.1), ("up", "decode")),
            # up beats down across pools
            (sig(pq=5.0, sess=0.2), ("up", "prefill")),
            (sig(sess=0.9, pq=0.5), ("up", "decode")),
            # prefill-down is evaluated before decode-down
            (sig(pq=0.5, sess=0.2), ("down", "prefill")),
            # a pool with no snapshot data contributes nothing
            (sig(pq=9.0, npf=0), None),
            (sig(kv=0.0, sess=1.0, ndc=0), None),
            (sig(pq=0.0, npf=0, ndc=0), None),
        ]
        for s, want in cases:
            assert a.decide_roles(s) == want, (s, want)

    def test_tick_scales_pools_independently(self):
        """e2e over stub replicas: a hot prefill queue spawns into the
        prefill pool (spawn hook receives the role), an idle prefill
        pool drains back — the decode pool is untouched either way."""
        pf = FakeReplica("pf0", queue_depth=9)
        dc = FakeReplica("dc0")
        pf.role, dc.role = "prefill", "decode"
        clock = [0.0]
        spawned = []

        def spawn(name, role):
            rep = FakeReplica(name)
            rep.role = role
            spawned.append((name, role))
            return rep

        rt = _router([pf, dc])
        a = self._scaler(rt, spawn, hysteresis=1, cooldown_s=0.0,
                         clock=lambda: clock[0])
        assert a.tick() == "up:prefill"
        assert spawned and spawned[-1][1] == "prefill"
        assert sorted(rt.roles.values()) == \
            ["decode", "prefill", "prefill"]
        # queues drain: the 2-replica prefill pool contracts; the
        # decode pool (1 replica) is never drained below one
        pf.queue_depth = 0
        clock[0] += 1.0
        assert a.tick() == "down:prefill"
        names = set(rt.alive_names())
        assert "dc0" in names
        assert sum(1 for n in names
                   if rt.roles.get(n) == "prefill") == 1
        # ... and the now-single prefill pool refuses to drain to zero
        clock[0] += 1.0
        assert a.tick() is None

    def test_pool_floor_repair_bypasses_hysteresis(self):
        """An empty pool (operator drain, replica death) is repaired on
        the NEXT tick regardless of hysteresis/cooldown — an empty
        prefill pool strands every new prompt, an empty decode pool
        strands every prefilled session."""
        pf = FakeReplica("pf0")
        pf.role = "prefill"
        spawned = []

        def spawn(name, role):
            rep = FakeReplica(name)
            rep.role = role
            spawned.append((name, role))
            return rep

        rt = _router([pf])
        a = self._scaler(rt, spawn, hysteresis=99, cooldown_s=1e9)
        assert a.tick() == "up:decode"     # decode pool was empty
        assert spawned[-1][1] == "decode"
        # both pools populated now: the huge hysteresis holds
        assert a.tick() is None


# =====================================================================
# RpcReplica: the same interface across a process boundary
# =====================================================================
class TestRpcReplica:
    def test_rpc_replica_parity_and_backpressure(self, monkeypatch):
        from paddle_tpu.core.native import load_native
        if load_native() is None:
            pytest.skip("native runtime unavailable")
        from paddle_tpu.distributed import rpc
        from paddle_tpu.serving_cluster import (RpcReplica, replica,
                                                serve_engine)
        # the served replica is the PROCESS's: this one's goes with the test
        monkeypatch.setattr(replica, "_WORKER", [None])

        fmt, embed, head = _model()
        rpc.init_rpc("cluster_worker0", rank=0, world_size=1,
                     master_endpoint="127.0.0.1:0")
        worker = None
        try:
            # world_size=1: the "remote" worker is this process's own
            # rpc agent — the full transport path (token preamble,
            # pickling, exception channel) without a subprocess
            worker = serve_engine(
                _engine(fmt, embed, head, max_pending=1),
                name="replica-rpc", threaded=False)
            rep = RpcReplica("cluster_worker0", ping_timeout=5)
            assert rep.alive
            prompt = [int(t) for t in
                      np.random.RandomState(5).randint(1, V, (10,))]
            want = _oracle(fmt, embed, head, prompt, 6)
            rid = rep.submit(prompt, max_new_tokens=6,
                             trace_id="trace-rpc-1", attempt=2)
            snap = rep.snapshot()
            assert snap["schema_version"] == SNAPSHOT_SCHEMA_VERSION
            assert snap["replica"] == "replica-rpc"
            # snapshot v2: the slo block crosses the wire too
            assert "slo" in snap and "objectives" in snap["slo"]
            # trace context PROPAGATES over rpc: the worker engine's
            # span carries the id/attempt the client submitted with
            dump = rep.trace_dump()
            assert dump["replica"] == "replica-rpc"
            sp = next(s for s in dump["spans"]
                      if s["trace_id"] == "trace-rpc-1")
            assert sp["attempt"] == 2
            # AdmissionFull crosses the rpc boundary AS AdmissionFull
            # (backpressure stays backpressure, never a transport error)
            long = [1] * 20
            with pytest.raises(AdmissionFull):
                for _ in range(5):
                    rep.submit(long, max_new_tokens=8)
            got, done = [], False
            deadline = time.monotonic() + WAIT_S
            while not done:
                assert time.monotonic() < deadline
                worker.pump()
                new, done, state = rep.harvest(rid)
                got += new
            assert got == want
            # a dead served replica surfaces as ReplicaError through
            # the live transport — the router's failover trigger
            worker.kill()
            with pytest.raises(ReplicaError):
                rep.submit(prompt, max_new_tokens=2)
        finally:
            rpc.shutdown()


# =====================================================================
# supervised worker gang (python -m paddle_tpu.serving_cluster --workers)
# =====================================================================
@pytest.mark.slow
def test_supervised_worker_gang_e2e(tmp_path):
    """The CLI's --workers recipe end to end: the supervisor spawns a
    worker process, rendezvouses it over rpc, fronts it with an
    RpcReplica, and serves a completion through the gateway — the
    promoted replacement for hand-rolled init_rpc glue."""
    import signal
    import sys
    import urllib.request

    from paddle_tpu.core.native import load_native
    from paddle_tpu.testing.child import Child, cpu_env
    if load_native() is None:
        pytest.skip("native runtime unavailable")
    with Child([sys.executable, "-m", "paddle_tpu.serving_cluster",
                "--workers", "1", "--port", "0",
                "--log-dir", str(tmp_path / "log")],
               env=cpu_env()) as supervisor:
        port = int(supervisor.wait_for(r"http://127\.0\.0\.1:(\d+)",
                                       timeout=WAIT_S).group(1))
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt": [5, 9, 2, 41],
                             "max_tokens": 8}).encode(),
            headers={"Content-Type": "application/json"})
        doc = json.load(urllib.request.urlopen(req, timeout=WAIT_S))
        toks = doc["choices"][0]["tokens"]
        assert len(toks) == 8
        # the worker engine serves the SAME weights as an in-process
        # replica would — the tokens match the local oracle (the CLI's
        # toy model: E,H,FF,L,V = 64,4,128,2,256, seed 0)
        paddle.seed(0)
        embed = Embedding(256, 64)
        fmt = FusedMultiTransformer(64, 4, 128, num_layers=2,
                                    normalize_before=True)
        head = Linear(64, 256, bias_attr=False)
        fmt.eval()
        dec = FusedDecoder(fmt, embed, head, max_seq_len=256)
        out = dec.generate(
            paddle.to_tensor(np.array([[5, 9, 2, 41]], np.int32)),
            max_new_tokens=8)
        want = [int(t) for t in np.asarray(out._data)[0, 4:]]
        assert toks == want
        assert supervisor.stop(signal.SIGINT, timeout=30) == 0


# =====================================================================
# structural pins
# =====================================================================
def test_http_surface_pinned(capsys):
    """tools/check_http_surface.py as a tier-1 test: every endpoint's
    field set and every error-status row asserted over live HTTP."""
    spec = importlib.util.spec_from_file_location(
        "check_http_surface",
        os.path.join(REPO_ROOT, "tools", "check_http_surface.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main()
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "ok" in out


def test_gateway_env_registry_complete():
    """Every PADDLE_GATEWAY_*/PADDLE_ROUTER_*/PADDLE_SLO_*/
    PADDLE_AUTOSCALE_*/PADDLE_QOS_*/PADDLE_TENANT_*/PADDLE_ROLE*/
    PADDLE_SERVING_MESH_* env the serving stack reads is registered in
    testing.GW_ENV_VARS (the conftest leak guard's list), and the
    registry carries no dead entries — same structural discipline as
    FI_ENV_VARS/FR_ENV_VARS. The SLO knobs live in
    inference/telemetry.py (SloPolicy.from_env) and the QoS shares +
    engine role in inference/serving.py, so both files join the scan;
    the autoscale knobs live in serving_cluster/autoscale.py (already
    in the package scan); the RPC client timeouts are read by
    serving_cluster/replica.py (RpcReplica), also in the package scan;
    the serving-mesh knobs are read by parallel/__init__.py
    (init_serving_mesh) and inference/generation.py (the weight-shard
    placement), so those two join the scan as well."""
    import re

    import paddle_tpu.inference.generation as gen_mod
    import paddle_tpu.inference.serving as serving_mod
    import paddle_tpu.inference.telemetry as tele_mod
    import paddle_tpu.parallel as par_mod
    import paddle_tpu.serving_cluster as sc
    from paddle_tpu.testing import GW_ENV_VARS
    pkg = os.path.dirname(os.path.abspath(sc.__file__))
    paths = [os.path.join(pkg, fn) for fn in os.listdir(pkg)
             if fn.endswith(".py")]
    paths.append(os.path.abspath(tele_mod.__file__))
    paths.append(os.path.abspath(serving_mod.__file__))
    paths.append(os.path.abspath(par_mod.__file__))
    paths.append(os.path.abspath(gen_mod.__file__))
    found = set()
    for path in paths:
        with open(path) as f:
            found |= set(re.findall(
                r"PADDLE_(?:(?:GATEWAY|ROUTER|SLO|AUTOSCALE|QOS"
                r"|TENANT|ROLE|RPC|SERVING_MESH)_[A-Z_0-9]+|ROLE\b)",
                f.read()))
    # the rpc-replica probe knob lives in replica.py; bench/tests may
    # reference more — the guard list must cover everything READ here
    assert found <= set(GW_ENV_VARS), (
        f"unregistered gateway env vars: {found - set(GW_ENV_VARS)} — "
        "add them to paddle_tpu.testing.GW_ENV_VARS")
    assert set(GW_ENV_VARS) <= found, (
        f"dead GW_ENV_VARS entries: {set(GW_ENV_VARS) - found}")
    # the SLO registry constant in telemetry.py must agree with the
    # guard list (one source of truth for the knob names)
    from paddle_tpu.inference.telemetry import SLO_ENV_VARS
    assert set(SLO_ENV_VARS) <= set(GW_ENV_VARS)


# =====================================================================
# gray-failure defense: health scoring, circuit breaker, hedging
# =====================================================================
class RecordingReplica(FakeReplica):
    """FakeReplica + scripted harvests, recorded releases, a snapshot
    failure switch (the flake/breaker lever), and a ``do_sample`` flag
    in the snapshot (the hedge safety gate reads it off the wire)."""

    def __init__(self, name, script=None, do_sample=False, **kw):
        super().__init__(name, **kw)
        self.script = list(script or [])
        self.do_sample = do_sample
        self.fail_snap = False
        self.released = []

    def snapshot(self):
        if self.fail_snap:
            raise ReplicaError(f"{self.name}: injected snapshot flake")
        snap = super().snapshot()
        snap["do_sample"] = self.do_sample
        return snap

    def harvest(self, rid):
        if self.script:
            return self.script.pop(0)
        return [], False, "running"

    def release(self, rid):
        self.released.append(rid)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestGrayFailureDefense:
    def test_snapshot_flake_keeps_replica_alive(self):
        """Contract: ONE failed snapshot drops the snapshot (the
        replica scores worst until it answers again) but must NOT mark
        the replica dead — and one flake alone must not open the
        breaker either."""
        a, b = RecordingReplica("a"), RecordingReplica("b")
        r = _router([a, b], policy="least_loaded", hedge_quantile=0)
        r.refresh(force=True)
        assert r._snap("a") is not None
        a.fail_snap = True
        r.refresh(force=True)
        assert "a" in r.alive_names()
        assert r._snap("a") is None
        assert r.breaker_state("a") == "closed"
        a.fail_snap = False
        r.refresh(force=True)
        assert r._snap("a") is not None

    def test_raced_death_placement_runs_failover(self):
        """Deterministic replay of the submit/mark_dead race: the
        replica is declared dead AFTER its engine accepted the request
        but BEFORE the router's bookkeeping wrote the placement.
        mark_dead's drain skips the still-pending assignment (replica
        is None), so submit() itself must detect the raced death and
        run the failover — the request may not strand on the corpse."""
        class DiesOnSubmit(RecordingReplica):
            router = None

            def submit(self, prompt, **kw):
                rid = super().submit(prompt, **kw)
                self.router.mark_dead(self.name)
                return rid

        a = DiesOnSubmit("a")
        b = RecordingReplica("b", queue_depth=5,
                             script=[([3, 4], True, "finished")])
        r = _router([a, b], policy="least_loaded", hedge_quantile=0)
        a.router = r
        gid = r.submit([1, 2])
        assert r.poll(gid)["replica"] == "b"
        assert r.failovers_total == 1
        assert "a" in r.dead
        toks, done, _ = r.harvest(gid)
        assert toks == [3, 4] and done
        # exactly one engine-side submission landed on each replica:
        # the corpse's accepted request was replayed once, not re-driven
        assert len(a.submitted) == 1 and len(b.submitted) == 1

    def test_breaker_opens_sheds_and_recovers(self):
        """closed -> open on accumulated snapshot errors (replica stays
        ALIVE), open sheds from placement, cooldown -> half_open admits
        exactly breaker_probes probe placements, and a healthy probe
        first-token closes the breaker — no operator action anywhere."""
        clk = _Clock()
        a = RecordingReplica("a", script=[([7], True, "finished")])
        b = RecordingReplica("b", queue_depth=5)
        r = _router([a, b], policy="least_loaded", clock=clk,
                    breaker_errs=2, breaker_cooldown_s=5.0,
                    breaker_probes=1, hedge_quantile=0)
        a.fail_snap = True
        r.refresh(force=True)
        clk.t += 1.0
        r.refresh(force=True)
        assert r.breaker_state("a") == "open"
        assert "a" in r.alive_names()          # shed, NOT dead
        a.fail_snap = False
        # placement avoids the open breaker though a is less loaded
        gid = r.submit([1, 2, 3])
        assert r.poll(gid)["replica"] == "b"
        # cooldown elapses -> half_open admits ONE probe placement
        clk.t += 10.0
        gid2 = r.submit([4, 5, 6])
        assert r.poll(gid2)["replica"] == "a"
        assert r.breaker_state("a") == "half_open"
        # with the probe outstanding further placements stay off a
        gid3 = r.submit([7, 8, 9])
        assert r.poll(gid3)["replica"] == "b"
        # the probe's first token closes the breaker
        clk.t += 0.01
        toks, done, _ = r.harvest(gid2)
        assert toks == [7] and done
        assert r.breaker_state("a") == "closed"
        assert r.breaker_transitions == {"open": 1, "half_open": 1,
                                         "closed": 1}

    def test_health_verdicts_are_median_relative(self):
        """A replica whose latency signal is a breaker_ratio outlier
        against the cluster median reads degraded, and check_health
        opens its breaker (shed while still alive and heartbeating)."""
        reps = [RecordingReplica(n) for n in ("a", "b", "c")]
        r = _router(reps, hedge_quantile=0)
        r.refresh(force=True)
        with r._lock:
            for _ in range(3):
                r._observe_ttft("a", 0.01)
                r._observe_ttft("b", 0.012)
                r._observe_ttft("c", 0.4)      # ~33x median: degraded
        st = r.health_status()
        assert st["a"]["verdict"] == "healthy"
        assert st["c"]["verdict"] == "degraded"
        assert r.check_health() == []          # nobody DIES
        assert r.breaker_state("c") == "open"
        assert "c" in r.alive_names()

    def _hedge_router(self, a, b, clk, **kw):
        kw.setdefault("policy", "least_loaded")
        kw.setdefault("hedge_quantile", 95)
        kw.setdefault("hedge_margin", 1.0)
        kw.setdefault("hedge_min_s", 0.001)
        r = _router([a, b], clock=clk, **kw)
        for _ in range(8):                     # cluster TTFT history
            r.hist_ttft.observe(0.001)
        return r

    def test_hedge_wins_and_loser_is_released(self):
        """A greedy request whose owner is silent past the cluster's
        own p95 TTFT is speculatively re-submitted; the hedge leg's
        first token wins, the original leg is aborted through the
        normal release path, and its tokens never reach the stream."""
        clk = _Clock()
        a = RecordingReplica("a")              # silent gray owner
        b = RecordingReplica("b", queue_depth=5,
                             script=[([5, 6], True, "finished")])
        r = self._hedge_router(a, b, clk)
        gid = r.submit([1, 2, 3])
        assert r.poll(gid)["replica"] == "a"
        toks, done, _ = r.harvest(gid)         # not overdue yet
        assert toks == [] and not done and r.hedges_total == 0
        clk.t += 1.0                           # way past p95 * margin
        r.harvest(gid)                         # arms the hedge
        assert r.hedges_total == 1
        rid_a = a.submitted[0][0]
        toks, done, _ = r.harvest(gid)         # hedge leg polls + wins
        assert toks == [5, 6] and done
        assert r.hedge_wins_total == 1
        assert rid_a in a.released             # loser leg aborted
        assert r.audit_counts["hedge"] == 1
        assert r.poll(gid)["resubmits"] == 1

    def test_hedge_on_real_engines_token_identical_zero_retraces(self):
        """The gray drill's count gates on REAL engines (virtual clock):
        the owner is alive but never pumped, the hedge leg on the other
        replica wins, the client sees exactly the oracle's greedy
        tokens (no loser token double-billed), nothing is declared dead
        or failed over, and warm engines trace nothing new for any of
        it."""
        fmt, embed, head = _model()
        clk = _Clock()
        reps = [LocalReplica(n, _engine(fmt, embed, head), threaded=False,
                             clock=clk) for n in ("a", "b")]
        r = self._hedge_router(*reps, clk, policy="round_robin",
                               hb_dead_s=1e9)
        by_name = {rep.name: rep for rep in reps}

        def run(seed):
            prompt = [int(t) for t in
                      np.random.RandomState(seed).randint(1, V, (10,))]
            gid = r.submit(prompt, max_new_tokens=6)
            owner = r.poll(gid)["replica"]
            other = by_name["b" if owner == "a" else "a"]
            assert r.harvest(gid)[0] == []     # not overdue yet
            clk.t += 1.0                       # way past p95 x margin
            r.harvest(gid)                     # arms the hedge
            got, done = [], False
            deadline = time.monotonic() + WAIT_S
            while not done:
                assert time.monotonic() < deadline
                other.pump()                   # the owner stays silent
                new, done, state = r.harvest(gid)
                got += new
            assert state == "finished"
            assert got == _oracle(fmt, embed, head, prompt, 6)
            assert r.poll(gid)["replica"] == other.name
            # the released loser leg runs out on its own engine; none
            # of its tokens reach the stream
            while by_name[owner].engine.has_work:
                assert time.monotonic() < deadline
                by_name[owner].pump()
            assert r.harvest(gid) == ([], True, "finished")
            return owner

        owners = {run(0), run(1)}              # warm-up: both directions
        assert owners == {"a", "b"}
        traces = [rep.engine.metrics()["traces"] for rep in reps]
        run(2)
        run(3)
        assert [rep.engine.metrics()["traces"] for rep in reps] == traces
        assert r.hedges_total == r.hedge_wins_total == 4
        assert r.failovers_total == 0 and not r.dead

    def test_hedge_loses_when_owner_answers_first(self):
        """The owner producing its first token makes the hedge leg the
        loser: released immediately, zero hedge wins, and the stream is
        exactly the owner's (no duplicate tokens)."""
        clk = _Clock()
        a = RecordingReplica("a", script=[([], False, "running"),
                                          ([], False, "running"),
                                          ([9], True, "finished")])
        b = RecordingReplica("b", queue_depth=5)   # hedge target, silent
        r = self._hedge_router(a, b, clk)
        gid = r.submit([1, 2, 3])
        r.harvest(gid)
        clk.t += 1.0
        r.harvest(gid)                         # arms the hedge -> b
        assert r.hedges_total == 1
        rid_b = b.submitted[0][0]
        toks, done, _ = r.harvest(gid)         # owner answers
        assert toks == [9] and done
        assert r.hedge_wins_total == 0
        assert rid_b in b.released             # loser leg aborted
        assert b.released.count(rid_b) == 1

    def test_sampled_requests_never_hedge(self):
        """Sampling re-draws the per-request seed on each engine
        submit, so two legs would diverge and the delivered stream
        would depend on the race — the gate reads do_sample off the v6
        snapshot and refuses."""
        clk = _Clock()
        a = RecordingReplica("a", do_sample=True)
        b = RecordingReplica("b", queue_depth=5, do_sample=True)
        r = self._hedge_router(a, b, clk)
        gid = r.submit([1, 2, 3])
        clk.t += 5.0
        r.harvest(gid)
        r.harvest(gid)
        assert r.hedges_total == 0

    def test_hedge_respects_retry_budget(self):
        """An empty cluster-wide retry budget blocks the speculative
        hedge (and counts the refusal); death failovers still proceed
        — they are the stream's only copy."""
        clk = _Clock()
        a = RecordingReplica("a")
        b = RecordingReplica("b", queue_depth=5)
        r = self._hedge_router(a, b, clk, retry_rate=0.0,
                               retry_burst=0)
        gid = r.submit([1, 2, 3])
        clk.t += 5.0
        r.harvest(gid)
        r.harvest(gid)
        assert r.hedges_total == 0
        assert r.retry_budget_exhausted_total >= 1

    def test_hedged_away_probe_reopens_breaker(self):
        """A half-open breaker PROBE that gets hedged away before its
        first token IS the probe verdict: the loser observation
        carries the probe gid, the outlier pending age re-opens the
        breaker, and the probe slot is freed — without this, the
        vanished probe wedges the breaker half-open forever."""
        clk = _Clock()
        a = RecordingReplica("a")              # silent owner
        b = RecordingReplica("b", queue_depth=5,
                             script=[([5], False, "running")])
        r = self._hedge_router(a, b, clk, breaker_errs=2,
                               breaker_cooldown_s=5.0,
                               breaker_probes=1)
        with r._lock:
            for _ in range(3):                 # b's healthy signal
                r._observe_ttft("b", 0.001)
        a.fail_snap = True
        r.refresh(force=True)
        clk.t += 1.0
        r.refresh(force=True)
        assert r.breaker_state("a") == "open"
        a.fail_snap = False
        clk.t += 10.0                          # cooldown elapses
        gid = r.submit([1, 2, 3])              # the probe placement
        assert r.poll(gid)["replica"] == "a"
        assert r.breaker_state("a") == "half_open"
        rid_a = a.submitted[0][0]
        clk.t += 1.0
        r.harvest(gid)                         # overdue: hedge -> b
        assert r.hedges_total == 1
        clk.t += 0.001
        toks, done, _ = r.harvest(gid)         # hedge wins, a loses
        assert toks == [5]
        assert r.hedge_wins_total == 1
        assert rid_a in a.released             # probe leg aborted
        assert r.breaker_state("a") == "open"  # probe verdict: failed

    def test_released_probe_frees_the_probe_slot(self):
        """A probe released before any first token must not occupy
        the half-open breaker's probe slot forever: _breaker_admits
        prunes gids that no longer live on the replica, so the next
        placement can probe again."""
        clk = _Clock()
        a = RecordingReplica("a")
        b = RecordingReplica("b", queue_depth=5)
        r = _router([a, b], policy="least_loaded", clock=clk,
                    breaker_errs=2, breaker_cooldown_s=5.0,
                    breaker_probes=1, hedge_quantile=0)
        a.fail_snap = True
        r.refresh(force=True)
        clk.t += 1.0
        r.refresh(force=True)
        a.fail_snap = False
        clk.t += 10.0
        gid = r.submit([1, 2, 3])
        assert r.poll(gid)["replica"] == "a"
        assert r.breaker_state("a") == "half_open"
        r.release(gid)                         # client went away
        gid2 = r.submit([4, 5, 6])             # slot freed: probe again
        assert r.poll(gid2)["replica"] == "a"
        assert r.breaker_state("a") == "half_open"
