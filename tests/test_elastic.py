"""Elastic cluster: live session migration, gateway-driven autoscaling,
and the drain fault path.

Contracts under test (all deterministic — virtual clocks, unthreaded
replicas, fault injection via env, no real-time sleeps):

  * ENGINE migration: ``export_slot``/``import_slot`` move a live
    request's KV blocks + decode state between engines with exact
    greedy (and plain-sampled) token parity, zero prefill recompute,
    and clean pool accounting on both sides (conftest
    ``check_serving_metrics`` reconciles refcounts after every move);
  * ROUTER drain: ``remove_replica`` = migrate-then-retire — the
    delivered-prefix skip keeps the client stream exactly-once, the
    audit ring records ``migrated``/``scale_down``, idempotent HTTP
    retries keep working across the drain, and a drain with nowhere to
    go orphans honestly (never hangs);
  * ``add_replica`` ring join moves ONLY the new replica's keys;
  * kill-mid-migration (``PADDLE_FI_AT_POINT=migration`` +
    ``PADDLE_FI_RAISE``) degrades to classic failover: no hang, no
    block leak, no double-delivered token;
  * the Autoscaler's watermark/hysteresis/cooldown logic and its
    spawn/drain integration with the router;
  * ``Router.retry_after_s`` stays within the protocol bounds.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference.serving import AdmissionFull, ServingEngine
from paddle_tpu.nn.layer.common import Embedding, Linear
from paddle_tpu.serving_cluster import (Autoscaler, LocalReplica,
                                        NoReplicaError, Router)
from paddle_tpu.testing import fault
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
# engine-level migration
# =====================================================================
class TestEngineMigration:
    def test_greedy_midstream_parity_and_pool_accounting(
            self, serving_metrics_ok):
        fmt, embed, head = _model()
        prompt = np.asarray(_prompt(12), np.int32)
        base = _engine(fmt, embed, head)
        rid = base.submit(prompt, max_new_tokens=20)
        base.run()
        want = [int(t) for t in base.results[rid]["tokens"]]

        a, b = _engine(fmt, embed, head), _engine(fmt, embed, head)
        rid = a.submit(prompt, max_new_tokens=20)
        deadline = time.monotonic() + WAIT_S
        while len(a._req_index[rid].tokens) < 5:
            assert time.monotonic() < deadline
            a.step()
        state = a.export_slot(rid)
        # the source released EVERYTHING it held for the slot
        assert a.pool.used == 0
        assert a._kv_reserved == 0 and a._kv_committed == 0
        assert rid not in a._req_index
        # the payload covers exactly the written KV
        assert state["lens"] > 0
        assert len(state["kv"]) == -(-state["lens"] // a.prefill_cap)
        rid2 = b.import_slot(state)
        b.run()
        got = [int(t) for t in b.results[rid2]["tokens"]]
        assert got == want                 # token-identical, incl. the
        ma = serving_metrics_ok(a)         # pre-migration prefix
        mb = serving_metrics_ok(b)
        assert ma["requests_migrated_out"] == 1
        assert ma["requests_finished"] == 0
        assert mb["requests_migrated_in"] == 1
        # ZERO re-prefill: the target never computed a prompt token
        assert mb["requests_admitted"] == 0
        assert b._prefill_tokens_computed == 0
        assert b.pool.used == 0            # finished slot freed its blocks

    def test_sampled_migration_stream_consistent(self):
        """Plain sampled mode (no spec): the per-request seed ships and
        every draw is fold_in(seed, nt), so the migrated continuation
        matches the unmigrated stream exactly."""
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

        a, b = mk(), mk()
        rid = a.submit(prompt, max_new_tokens=16)
        # force the SAME per-request seed as the baseline (each submit
        # draws a fresh one off the global key stream)
        a._req_index[rid].seed = seed0
        deadline = time.monotonic() + WAIT_S
        while len(a._req_index[rid].tokens) < 4:
            assert time.monotonic() < deadline
            a.step()
        a._rseed[a._req_index[rid].slot] = seed0
        state = a.export_slot(rid)
        assert state["seed"] == seed0      # the sampler seed migrates
        rid2 = b.import_slot(state)
        b.run()
        assert [int(t) for t in b.results[rid2]["tokens"]] == want

    def test_midprefill_migration_completes(self, serving_metrics_ok):
        """A slot exported MID-PREFILL (budget scheduler, pf_left > 0)
        resumes prefilling on the target and still matches the
        oracle."""
        fmt, embed, head = _model()
        # tiny token budget: a 40-token prompt needs several dispatches
        a = _engine(fmt, embed, head, token_budget=8)
        b = _engine(fmt, embed, head, token_budget=8)
        prompt = np.asarray(_prompt(40, seed=11), np.int32)
        want = _oracle(fmt, embed, head, [int(t) for t in prompt], 8)
        rid = a.submit(prompt, max_new_tokens=8)
        a.step()                           # some prefill, no tokens yet
        req = a._req_index[rid]
        assert req.slot is not None and a._pf_left[req.slot] > 0
        state = a.export_slot(rid)
        assert state["pf_left"] > 0 and not state["tokens"]
        rid2 = b.import_slot(state)
        b.run()
        assert [int(t) for t in b.results[rid2]["tokens"]] == want
        serving_metrics_ok(a)
        serving_metrics_ok(b)

    def test_queued_export_requeues_on_target(self, serving_metrics_ok):
        fmt, embed, head = _model()
        a, b = _engine(fmt, embed, head), _engine(fmt, embed, head)
        prompt = np.asarray(_prompt(10), np.int32)
        want = _oracle(fmt, embed, head, [int(t) for t in prompt], 6)
        # fill both slots, then queue a third request
        for _ in range(2):
            a.submit(_prompt(8, seed=1), max_new_tokens=4)
        rid = a.submit(prompt, max_new_tokens=6)
        state = a.export_slot(rid)
        assert state["kv"] == [] and state["lens"] == 0
        rid2 = b.import_slot(state)
        assert b.queue_depth == 1          # re-queued, admitted normally
        a.run()
        b.run()
        assert [int(t) for t in b.results[rid2]["tokens"]] == want
        ma = serving_metrics_ok(a)
        mb = serving_metrics_ok(b)
        assert ma["requests_migrated_out"] == 1
        assert mb["requests_migrated_in"] == 1
        # the re-queued import IS an admission (and one prefix lookup)
        assert mb["requests_admitted"] == 1

    def test_import_sheds_honestly_and_leaks_nothing(
            self, serving_metrics_ok):
        fmt, embed, head = _model()
        a = _engine(fmt, embed, head)
        b = _engine(fmt, embed, head, num_slots=1)
        # occupy the target's only slot
        b.submit(_prompt(8, seed=2), max_new_tokens=60)
        b.step()
        rid = a.submit(_prompt(10), max_new_tokens=8)
        while not a._req_index[rid].tokens:
            a.step()
        state = a.export_slot(rid)
        used_before = b.pool.used
        with pytest.raises(AdmissionFull):
            b.import_slot(state)
        assert b.pool.used == used_before  # failed import leaks nothing
        serving_metrics_ok(b)
        # the state is still importable elsewhere
        c = _engine(fmt, embed, head)
        c.import_slot(state)
        c.run()
        serving_metrics_ok(c)

    def test_import_validates_layout(self):
        fmt, embed, head = _model()
        a = _engine(fmt, embed, head)
        b = _engine(fmt, embed, head, prefill_cap=16)
        rid = a.submit(_prompt(10), max_new_tokens=8)
        while not a._req_index[rid].tokens:
            a.step()
        state = a.export_slot(rid)
        with pytest.raises(ValueError, match="prefill_cap"):
            b.import_slot(state)
        with pytest.raises(ValueError, match="migration state"):
            b.import_slot({"fmt": "nonsense"})
        # a corrupt lens past the request's own budget must shed HERE
        # with a readable error, not over-commit the pool later
        c = _engine(fmt, embed, head)
        bad = dict(state)
        bad["lens"] = int(bad["prompt"].size) + bad["max_new_tokens"] + 1
        with pytest.raises(ValueError, match="budget"):
            c.import_slot(bad)
        dense = _engine(fmt, embed, head, paged=False)
        with pytest.raises(ValueError, match="paged"):
            dense.export_slot(0)
        with pytest.raises(ValueError, match="paged"):
            dense.import_slot(state)


# =====================================================================
# router: elastic replica set
# =====================================================================
def _cluster(fmt, embed, head, n=2, clock=None, **rkw):
    ck = clock or (lambda: 0.0)
    reps = [LocalReplica(f"replica{i}", _engine(fmt, embed, head),
                         threaded=False, clock=ck)
            for i in range(n)]
    rkw.setdefault("policy", "round_robin")
    rkw.setdefault("hb_dead_s", 1e9)
    rkw.setdefault("snap_max_age_s", 0.0)
    return reps, Router(reps, clock=ck, **rkw)


class TestRouterElastic:
    def test_add_replica_minimal_key_movement(self):
        """Scale-up rebalance pin: joining a replica moves ONLY the
        keys its vnodes claim — every other template keeps its home
        (and its hot radix chain)."""
        fmt, embed, head = _model()
        reps, router = _cluster(fmt, embed, head, n=3)
        keys = [f"template-{i}".encode() for i in range(256)]
        before = {k: router.ring.owner(k) for k in keys}
        clock = [0.0]
        new = LocalReplica("replica9", _engine(fmt, embed, head),
                           threaded=False, clock=lambda: clock[0])
        router.add_replica(new)
        after = {k: router.ring.owner(k) for k in keys}
        moved = [k for k in keys if before[k] != after[k]]
        assert moved and all(after[k] == "replica9" for k in moved)
        assert "replica9" in router.placeable_names()
        assert router.audit_counts["scale_up"] == 1
        assert router.scale_events["up"] == 1
        with pytest.raises(ValueError):
            router.add_replica(new)        # already registered + alive

    def test_remove_replica_live_migrates_exactly_once(self):
        """THE drain contract: harvest 3 tokens, drain the owner, and
        the stream continues on the replacement token-identically with
        no duplicate and no gap — via MIGRATION (zero failovers, zero
        target prefill recompute), attempt bumped like a failover."""
        fmt, embed, head = _model()
        clock = [0.0]
        reps, router = _cluster(fmt, embed, head, n=2,
                                clock=lambda: clock[0])
        prompt = _prompt(10)
        want = _oracle(fmt, embed, head, prompt, 20)
        gid = router.submit(prompt, max_new_tokens=20,
                            trace_id="trace-migrate-1")
        victim = router._table[gid].replica
        vrep = router.replicas[victim]
        got = []
        deadline = time.monotonic() + WAIT_S
        while len(got) < 3:
            assert time.monotonic() < deadline
            vrep.pump()
            got += router.harvest(gid)[0]
        summary = router.remove_replica(victim)
        assert summary == {"replica": victim, "migrated": 1,
                           "failed_over": 0, "orphaned": 0,
                           "expired": 0}
        assert router.migrations_total == 1
        assert router.failovers_total == 0
        assert victim not in router.replicas   # retired, not dead
        other_name = router._table[gid].replica
        assert other_name != victim
        other = router.replicas[other_name]
        assert other.engine.metrics()["prefill_tokens_computed"] == 0
        assert other.engine.metrics()["requests_migrated_in"] == 1
        # same trace id, next attempt — the merged trace joins the move
        assert router.poll(gid)["trace_id"] == "trace-migrate-1"
        assert router.poll(gid)["attempt"] == 2
        done = False
        while not done:
            assert time.monotonic() < deadline
            other.pump()
            new, done, state = router.harvest(gid)
            got += new
        assert got == want                 # exactly-once, no gap, no dup
        assert state == "finished"
        # the audit ring recorded the migration and the scale-down
        assert router.audit_counts["migrated"] == 1
        assert router.audit_counts["scale_down"] == 1
        reasons = [e["reason"] for e in router.audit]
        assert "migrated" in reasons and "scale_down" in reasons

    def test_warm_migration_traces_nothing_and_accounts_every_prompt_token(
            self):
        """The scale drill's two count gates that no other test held: a
        drain between WARM replicas (each has already exported and
        imported once) traces nothing new on either, and over the whole
        drill prefill computed + saved == prompt tokens submitted (a
        migrated stream ships KV, it never replays its prompt)."""
        fmt, embed, head = _model()
        clock = [0.0]
        # the prefill counters run where a prefix cache is configured
        reps = [LocalReplica(f"replica{i}",
                             _engine(fmt, embed, head,
                                     prefix_cache_blocks=8),
                             threaded=False, clock=lambda: clock[0])
                for i in range(2)]
        router = Router(reps, policy="round_robin", hb_dead_s=1e9,
                        snap_max_age_s=0.0, clock=lambda: clock[0])
        submitted = [0]

        def migrate_once(seed):
            prompt = _prompt(10, seed=seed)
            submitted[0] += len(prompt)
            want = _oracle(fmt, embed, head, prompt, 8)
            gid = router.submit(prompt, max_new_tokens=8)
            victim = router._table[gid].replica
            vrep = router.replicas[victim]
            got = []
            deadline = time.monotonic() + WAIT_S
            while len(got) < 3:
                assert time.monotonic() < deadline
                vrep.pump()
                got += router.harvest(gid)[0]
            summary = router.remove_replica(victim)
            assert summary["migrated"] == 1 and \
            summary["failed_over"] == 0, summary
            other = router.replicas[router._table[gid].replica]
            done = False
            while not done:
                assert time.monotonic() < deadline
                other.pump()
                new, done, _ = router.harvest(gid)
                got += new
            assert got == want
            # the drain closed the handle: the same (warm) engine comes
            # back under the retired name, as a replaced process would
            router.add_replica(LocalReplica(
                victim, vrep.engine, threaded=False,
                clock=lambda: clock[0]))
            return victim

        victims = [migrate_once(seed) for seed in (11, 12, 13, 14)]
        engines = [r.engine for r in reps]
        assert {r.name for r in reps} <= set(victims), victims
        traces = [e.metrics()["traces"] for e in engines]
        victims += [migrate_once(seed) for seed in (15, 16)]
        assert [e.metrics()["traces"] for e in engines] == traces
        assert router.migrations_total == 6
        assert router.migration_aborts_total == 0
        assert router.failovers_total == 0
        ms = [e.metrics() for e in engines]
        assert sum(m["prefill_tokens_computed"]
                   + m["prefill_tokens_saved"] for m in ms) == submitted[0]

    def test_deadline_survives_repeated_migration(self):
        """A deadline_s stream migrated TWICE keeps its real remaining
        budget: every leg computes remaining from the PRISTINE
        submit-time deadline. Subtracting elapsed-since-submit from the
        already-decremented exported value instead double-counts each
        earlier leg and expires a stream with budget to spare."""
        fmt, embed, head = _model()
        clock = [0.0]
        reps, router = _cluster(fmt, embed, head, n=3,
                                clock=lambda: clock[0])
        prompt = _prompt(10)
        want = _oracle(fmt, embed, head, prompt, 20)
        gid = router.submit(prompt, max_new_tokens=20, deadline_s=10.0)

        def owner():
            return router._table[gid].replica

        got = []
        deadline = time.monotonic() + WAIT_S
        while len(got) < 3:
            assert time.monotonic() < deadline
            router.replicas[owner()].pump()
            got += router.harvest(gid)[0]
        clock[0] = 4.0                 # leg 1 used 4s of the 10s budget
        first = owner()
        router.remove_replica(first)
        assert owner() != first
        clock[0] = 6.0                 # 6s elapsed total, 4s remaining
        router.remove_replica(owner())
        # the buggy math had remaining = (10-4) - 6 = 0 -> "expired"
        assert router.migrations_total == 2
        done = False
        while not done:
            assert time.monotonic() < deadline
            router.replicas[owner()].pump()
            new, done, state = router.harvest(gid)
            got += new
        assert state == "finished"
        assert got == want

    def test_drain_counts_expired_stream(self):
        """A deadline stream whose budget lapsed by drain time lands in
        the summary's 'expired' slot — not silently dropped from the
        /admin/drain accounting (DRAIN_FIELDS), not misfiled under
        failed_over."""
        fmt, embed, head = _model()
        clock = [0.0]
        reps, router = _cluster(fmt, embed, head, n=2,
                                clock=lambda: clock[0])
        gid = router.submit(_prompt(10), max_new_tokens=20,
                            deadline_s=5.0)
        victim = router._table[gid].replica
        vrep = router.replicas[victim]
        deadline = time.monotonic() + WAIT_S
        while not router.harvest(gid)[0]:
            assert time.monotonic() < deadline
            vrep.pump()
        clock[0] = 6.0                 # the 5s budget is gone
        summary = router.remove_replica(victim)
        assert summary["expired"] == 1
        assert summary["migrated"] == summary["failed_over"] == 0
        new, done, state = router.harvest(gid)
        assert done and state == "expired"

    def test_idempotent_retry_and_orphan_during_drain(self):
        """Satellite pin: an idempotent HTTP retry issued across a
        scale-down drain returns the ORIGINAL gid (same stream, same
        trace id), and a drain with no surviving replica orphans the
        assignment honestly — harvest raises NoReplicaError, the
        source engine leaks no blocks."""
        fmt, embed, head = _model()
        clock = [0.0]
        reps, router = _cluster(fmt, embed, head, n=2,
                                clock=lambda: clock[0])
        prompt = _prompt(10)
        gid = router.submit(prompt, max_new_tokens=20,
                            request_id="client-req-1")
        victim = router._table[gid].replica
        vrep = router.replicas[victim]
        got = []
        deadline = time.monotonic() + WAIT_S
        while len(got) < 3:
            assert time.monotonic() < deadline
            vrep.pump()
            got += router.harvest(gid)[0]
        router.remove_replica(victim)
        # the retry AFTER the drain: same gid, nothing re-submitted
        assert router.submit(prompt, max_new_tokens=20,
                             request_id="client-req-1") == gid
        assert router._table[gid].dup_returns == 1
        other = router.replicas[router._table[gid].replica]
        done = False
        while not done:
            assert time.monotonic() < deadline
            other.pump()
            new, done, _ = router.harvest(gid)
            got += new
        assert len(got) == 20

        # --- orphan half: drain the LAST replica (router-level API has
        # no gateway guard; the stream must orphan, never hang)
        last = router.placeable_names()[0]
        lrep = router.replicas[last]
        gid2 = router.submit(_prompt(8, seed=5), max_new_tokens=20)
        while not router.harvest(gid2)[0]:
            assert time.monotonic() < deadline
            lrep.pump()
        summary = router.remove_replica(last)
        assert summary["orphaned"] == 1
        assert router.migration_aborts_total >= 1
        with pytest.raises(NoReplicaError):
            router.harvest(gid2)
        # the export freed the source's blocks even though the
        # migration had nowhere to land
        assert lrep.engine.pool.used == 0

    def test_kill_mid_migration_falls_back_to_failover(
            self, monkeypatch, serving_metrics_ok):
        """The chaos satellite: PADDLE_FI_AT_POINT=migration kills the
        transfer BETWEEN export and import (state off the source, on no
        target). The drain must degrade to classic failover — stream
        finishes elsewhere exactly-once (replay, delivered prefix
        skipped), no hang, no stranded block on either engine."""
        fmt, embed, head = _model()
        clock = [0.0]
        reps, router = _cluster(fmt, embed, head, n=2,
                                clock=lambda: clock[0])
        prompt = _prompt(10)
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
        fault.reset()
        monkeypatch.setenv("PADDLE_FI_AT_POINT", "migration")
        monkeypatch.setenv("PADDLE_FI_RAISE", "0")
        try:
            summary = router.remove_replica(victim)
        finally:
            monkeypatch.delenv("PADDLE_FI_AT_POINT")
            monkeypatch.delenv("PADDLE_FI_RAISE")
            fault.reset()
        assert summary == {"replica": victim, "migrated": 0,
                           "failed_over": 1, "orphaned": 0,
                           "expired": 0}
        assert router.migration_aborts_total == 1
        assert router.migrations_total == 0
        assert router.failovers_total == 1
        other = router.replicas[router._table[gid].replica]
        done = False
        while not done:
            assert time.monotonic() < deadline
            other.pump()
            new, done, state = router.harvest(gid)
            got += new
        assert got == want                 # no double delivery, no gap
        assert state == "finished"
        # no stranded blocks anywhere: the source exported (blocks
        # freed), the fallback re-prefilled on the target
        assert vrep.engine.pool.used == 0
        serving_metrics_ok(vrep.engine)
        serving_metrics_ok(other.engine)
        assert other.engine.metrics()["requests_migrated_in"] == 0
        assert other.engine.metrics()["prefill_tokens_computed"] == 0 \
            or other.engine.prefix_cache is not None


# =====================================================================
# autoscaler
# =====================================================================
class TestAutoscaler:
    def _fake_router(self):
        """A minimal stand-in exposing exactly what Autoscaler reads."""
        class FakeRouter:
            def __init__(self):
                import threading
                self._lock = threading.RLock()
                self._snapshots = {}
                self.added = []
                self.removed = []

            def refresh(self):
                pass

            def placeable_names(self):
                return sorted(self._snapshots)

            def _snap(self, name):
                return self._snapshots.get(name)

            @staticmethod
            def load_score(snap):
                return 0 if snap is None else snap.get("queue_depth", 0)

            def add_replica(self, rep):
                self._snapshots[rep.name] = {"queue_depth": 0}
                self.added.append(rep.name)

            def remove_replica(self, name, migrate=True):
                self._snapshots.pop(name)
                self.removed.append(name)
                return {}
        return FakeRouter()

    def _spawn(self):
        class Rep:
            def __init__(self, name):
                self.name = name
        return Rep

    def test_decide_watermarks(self):
        r = self._fake_router()
        asc = Autoscaler(r, self._spawn(), min_replicas=1,
                         max_replicas=4, queue_high=4.0, queue_low=0.5,
                         kv_free_low=0.1, cooldown_s=10, hysteresis=2)
        asc._last_violated_queue = 0   # baseline seeded (first tick)
        sig = {"replicas": 2, "queue_mean": 5.0, "kv_free_frac": 1.0,
               "slo_violated_queue": 0}
        assert asc.decide(sig) == "up"             # queue pressure
        sig.update(queue_mean=1.0, kv_free_frac=0.05)
        assert asc.decide(sig) == "up"             # pool pressure
        sig.update(kv_free_frac=0.5, slo_violated_queue=3)
        assert asc.decide(sig) == "up"             # goodput pressure
        asc._last_violated_queue = 3
        assert asc.decide(sig) is None             # no NEW violations
        sig.update(queue_mean=0.2)
        assert asc.decide(sig) == "down"
        sig.update(queue_mean=1.0)
        assert asc.decide(sig) is None             # between watermarks

    def test_no_snapshot_data_holds(self):
        """A total snapshot outage (every placeable replica's fetch
        failed — e.g. busy rpc workers timing out the liveness probe
        under a load spike) zeroes the signals; that must HOLD, not
        read as an idle cluster and drain healthy, saturated
        capacity."""
        r = self._fake_router()
        Rep = self._spawn()
        r.add_replica(Rep("a"))
        r.add_replica(Rep("b"))
        asc = Autoscaler(r, Rep, min_replicas=1, max_replicas=4,
                         queue_high=4.0, queue_low=0.5, cooldown_s=0.0,
                         hysteresis=1, clock=lambda: 0.0)
        r._snapshots["a"] = r._snapshots["b"] = None   # outage
        sig = asc.signals()
        assert sig["snapshots"] == 0
        assert sig["queue_mean"] == 0.0
        assert asc.decide(sig) is None
        assert asc.tick() is None
        assert r.removed == []

    def test_preexisting_violations_are_baseline_not_delta(self):
        """slo.violated_queue is a CUMULATIVE window counter: the
        first tick seeds the baseline (attaching an autoscaler to a
        cluster with violation history must not spawn on a quiet
        cluster), only NEW violations scale up, and a snapshot outage
        must not zero the baseline (the full history would read as a
        fresh delta when the snapshots return)."""
        r = self._fake_router()
        Rep = self._spawn()
        r.add_replica(Rep("a"))
        asc = Autoscaler(r, Rep, min_replicas=1, max_replicas=4,
                         queue_high=4.0, queue_low=0.0, cooldown_s=0.0,
                         hysteresis=1, clock=lambda: 0.0)
        r._snapshots["a"] = {"queue_depth": 0,
                             "slo": {"violated_queue": 50}}
        assert asc.tick() is None      # history -> baseline, not delta
        r._snapshots["a"]["slo"]["violated_queue"] = 55
        assert asc.tick() == "up"      # 5 NEW violations
        for name in r._snapshots:      # total snapshot outage tick
            r._snapshots[name] = None
        assert asc.tick() is None
        r._snapshots["a"] = {"queue_depth": 0,
                             "slo": {"violated_queue": 55}}
        assert asc.tick() is None      # baseline survived the outage

    def test_floor_restored_after_external_drain(self):
        """min_replicas is an INVARIANT, not a load signal: an operator
        /admin/drain (guarded only against the last replica) can take
        the set below it, and no watermark ever fires on an idle
        cluster — the next tick must restore the floor, bypassing
        hysteresis AND cooldown."""
        r = self._fake_router()
        Rep = self._spawn()
        r.add_replica(Rep("a"))
        r.add_replica(Rep("b"))
        asc = Autoscaler(r, Rep, min_replicas=2, max_replicas=4,
                         queue_high=4.0, queue_low=0.5,
                         cooldown_s=100.0, hysteresis=2,
                         clock=lambda: 0.0)
        asc._last_scale_t = 0.0        # cooldown in force
        r.remove_replica("b")          # operator drain below the floor
        assert asc.tick() == "up"
        assert len(r.placeable_names()) == 2

    def test_vq_event_bypasses_hysteresis(self):
        """Goodput violations are event-shaped (the delta is consumed
        by the baseline update), so the consecutive-tick hysteresis
        meant for level signals could never be met by them alone —
        new violations must scale up in ONE tick."""
        r = self._fake_router()
        Rep = self._spawn()
        r.add_replica(Rep("a"))
        asc = Autoscaler(r, Rep, min_replicas=1, max_replicas=4,
                         queue_high=4.0, queue_low=0.0, cooldown_s=0.0,
                         hysteresis=2, clock=lambda: 0.0)
        r._snapshots["a"] = {"queue_depth": 0,
                             "slo": {"violated_queue": 0}}
        assert asc.tick() is None      # baseline seeded
        r._snapshots["a"]["slo"]["violated_queue"] = 1
        assert asc.tick() == "up"      # damage already done: one tick

    def test_hysteresis_cooldown_and_bounds(self):
        clock = [0.0]
        r = self._fake_router()
        Rep = self._spawn()
        r.add_replica(Rep("seed-replica"))
        asc = Autoscaler(r, Rep, min_replicas=1, max_replicas=2,
                         queue_high=2.0, queue_low=0.5, cooldown_s=5.0,
                         hysteresis=2, clock=lambda: clock[0])
        r._snapshots["seed-replica"]["queue_depth"] = 10
        assert asc.tick() is None          # hysteresis tick 1
        assert asc.tick() == "up"          # hysteresis satisfied
        assert r.added[-1].startswith("scaled-")
        r._snapshots[r.added[-1]]["queue_depth"] = 10
        assert asc.tick() is None          # streak reset after scaling
        assert asc.tick() is None          # cooldown blocks
        clock[0] += 6.0
        assert asc.tick() is None          # streak must rebuild...
        assert asc.tick() is None          # ...but max_replicas caps it
        for s in r._snapshots.values():
            s["queue_depth"] = 0
        clock[0] += 6.0
        asc.tick()
        assert asc.tick() == "down"
        clock[0] += 6.0
        asc.tick()
        assert asc.tick() is None          # min_replicas floor
        assert len(r.placeable_names()) == 1

    def test_validation(self):
        r = self._fake_router()
        with pytest.raises(ValueError, match="min"):
            Autoscaler(r, self._spawn(), min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError, match="queue_low"):
            Autoscaler(r, self._spawn(), queue_high=1.0, queue_low=2.0)
        with pytest.raises(ValueError, match="hysteresis"):
            Autoscaler(r, self._spawn(), hysteresis=0)

    def test_scale_to_walks_and_clamps(self):
        r = self._fake_router()
        Rep = self._spawn()
        r.add_replica(Rep("seed-replica"))
        asc = Autoscaler(r, Rep, min_replicas=1, max_replicas=3,
                         queue_high=2.0, queue_low=0.5,
                         clock=lambda: 0.0)
        assert asc.scale_to(5) == 3        # clamped to max
        assert len(r.placeable_names()) == 3
        assert asc.scale_to(1) == 1
        assert len(r.placeable_names()) == 1

    def test_real_router_up_down_cycle(self):
        """Integration on real engines: queue pressure grows the set,
        the drained tail shrinks it back, nothing is lost."""
        fmt, embed, head = _model()
        clock = [0.0]

        def ck():
            return clock[0]

        rep0 = LocalReplica("replica0", _engine(fmt, embed, head),
                            threaded=False, clock=ck)
        router = Router([rep0], policy="least_loaded", hb_dead_s=1e9,
                        snap_max_age_s=0.0, clock=ck)

        def spawn(name):
            return LocalReplica(name, _engine(fmt, embed, head),
                                threaded=False, clock=ck)
        asc = Autoscaler(router, spawn, min_replicas=1, max_replicas=2,
                         queue_high=1.0, queue_low=0.25,
                         cooldown_s=0.5, hysteresis=1, clock=ck)
        gids = [router.submit(_prompt(8, seed=i), max_new_tokens=4)
                for i in range(5)]
        clock[0] += 0.01
        assert asc.tick() == "up"
        assert len(router.placeable_names()) == 2
        deadline = time.monotonic() + WAIT_S
        while True:
            assert time.monotonic() < deadline
            reps = [router.replicas[n]
                    for n in router.placeable_names()]
            if not any(r.engine.has_work for r in reps):
                break
            for r in reps:
                r.pump()
            clock[0] += 0.002
        for g in gids:
            new, done, state = router.harvest(g)
            assert done and state == "finished"
        clock[0] += 1.0
        assert asc.tick() == "down"
        assert len(router.placeable_names()) == 1
        assert router.migration_aborts_total == 0


# =====================================================================
# dynamic Retry-After
# =====================================================================
class TestRetryAfter:
    def _router_with_snaps(self, snaps):
        router = Router([], snap_max_age_s=1e9)
        for i, s in enumerate(snaps):
            name = f"r{i}"
            router.replicas[name] = object()   # placeholder handle
            router._snaps[name] = (s, 0.0)
        return router

    def test_bounds_and_computation(self):
        from paddle_tpu.serving_cluster import protocol as P
        # no data / no backlog -> the floor
        assert Router([]).retry_after_s() == P.RETRY_AFTER_S
        r = self._router_with_snaps([{"queue_depth": 0}])
        assert r.retry_after_s() == P.RETRY_AFTER_S
        # backlog but no observed drain -> the cap
        r = self._router_with_snaps([{"queue_depth": 50}])
        r._drain_samples.extend([(0.0, 10), (5.0, 10)])
        assert r.retry_after_s() == P.RETRY_AFTER_MAX_S
        # measured drain: 12 queued at 4 finished/s -> ceil(3) = 3
        r = self._router_with_snaps([{"queue_depth": 12}])
        r._drain_samples.extend([(0.0, 0), (2.0, 8)])
        assert r.retry_after_s() == 3
        # huge queue at a trickle still caps
        r = self._router_with_snaps([{"queue_depth": 10000}])
        r._drain_samples.extend([(0.0, 0), (10.0, 1)])
        assert r.retry_after_s() == P.RETRY_AFTER_MAX_S
        # a negative step (replica retired mid-window) resets to floor
        r = self._router_with_snaps([{"queue_depth": 12}])
        r._drain_samples.extend([(0.0, 50), (2.0, 8)])
        assert r.retry_after_s() == P.RETRY_AFTER_S
        assert not r._drain_samples

    def test_refresh_records_drain_samples(self):
        fmt, embed, head = _model()
        clock = [0.0]
        reps, router = _cluster(fmt, embed, head, n=1,
                                clock=lambda: clock[0])
        router.refresh(force=True)
        assert len(router._drain_samples) == 1
        clock[0] += 1.0
        router.refresh()
        assert len(router._drain_samples) == 2
        # a submit/429-retry burst (refresh() runs per submit) must not
        # collapse the window to milliseconds: samples keep a minimum
        # spacing, so the measured drain rate stays meaningful
        for _ in range(40):
            clock[0] += 0.001
            router.refresh()
        assert len(router._drain_samples) == 2
        assert router._drain_samples[0][0] == 0.0


# =====================================================================
# migration across the rpc boundary
# =====================================================================
def test_rpc_migration_state_round_trip(monkeypatch):
    """The migration payload (numpy KV blocks + the contract) must
    pickle through the rpc transport intact: export over rpc from the
    served engine, import into a local engine, finish with oracle
    parity."""
    from paddle_tpu.core.native import load_native
    if load_native() is None:
        pytest.skip("native runtime unavailable")
    from paddle_tpu.distributed import rpc
    from paddle_tpu.serving_cluster import RpcReplica, replica, serve_engine
    # the served replica is the PROCESS's: this one's goes with the test
    monkeypatch.setattr(replica, "_WORKER", [None])

    fmt, embed, head = _model()
    rpc.init_rpc("elastic_worker0", rank=0, world_size=1,
                 master_endpoint="127.0.0.1:0")
    try:
        worker = serve_engine(_engine(fmt, embed, head),
                              name="replica-rpc", threaded=False)
        rep = RpcReplica("elastic_worker0", ping_timeout=5)
        prompt = _prompt(10)
        want = _oracle(fmt, embed, head, prompt, 12)
        rid = rep.submit(prompt, max_new_tokens=12)
        deadline = time.monotonic() + WAIT_S
        got = []
        while len(got) < 3:
            assert time.monotonic() < deadline
            worker.pump()
            got += rep.harvest(rid)[0]
        state = rep.export_slot(rid)       # KV bytes over the wire
        assert state["lens"] > 0 and state["kv"]
        target = _engine(fmt, embed, head)
        rid2 = target.import_slot(state)
        target.run()
        assert [int(t) for t in target.results[rid2]["tokens"]] == want
        # ... and the reverse direction: import over rpc
        rid3 = target.import_slot(
            {**state, "tokens": list(state["tokens"])})
        st2 = target.export_slot(rid3)
        rid4 = rep.import_slot(st2)
        done = False
        while not done:
            assert time.monotonic() < deadline
            worker.pump()
            new, done, s = rep.harvest(rid4)
        assert s == "finished"
    finally:
        rpc.shutdown()
