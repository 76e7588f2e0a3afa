"""Continuous-batching serving engine over the stacked KV ring cache.

Contracts under test:
  * token-for-token greedy parity: a request stream pushed through the
    engine's churning slots must produce EXACTLY the tokens sequential
    FusedDecoder.generate() calls produce (per-slot positions, masked
    in-slot prefill, and per-slot logit controls must all be invisible);
  * zero-recompile churn: slot free/re-admit is pure data — the engine's
    trace-count spy must not move after warmup;
  * the full-cache guard in the decode_attention write kernels (the
    eviction invariant the engine relies on): a row at cache_lens ==
    Smax drops the write instead of corrupting neighbouring blocks.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import jax.numpy as jnp

from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.nn.layer.common import Embedding, Linear
from paddle_tpu.testing.oracle import sequential_tokens

V, E, H, FF, L = 97, 32, 4, 64, 2


def _model(seed=3):
    paddle.seed(seed)
    embed = Embedding(V, E)
    fmt = FusedMultiTransformer(E, H, FF, num_layers=L,
                                normalize_before=True)
    head = Linear(E, V, bias_attr=False)
    fmt.eval()
    return fmt, embed, head


def _prompt(rng, n):
    return rng.randint(1, V, (n,)).astype(np.int32)


class TestServingParity:
    @pytest.mark.parametrize("bulk,rotary", [
        ("1", False), ("0", False), ("1", True), ("0", True)])
    def test_greedy_tokens_match_sequential_decode(self, monkeypatch,
                                                   bulk, rotary):
        """5 mixed-length requests churned through 2 slots == 5
        sequential FusedDecoder.generate() calls, token for token —
        for BOTH in-slot prefill flavors (bulk flash / masked scan) and,
        with rotary on, the vector-t rope branch (each slot's rope at
        its OWN per-row position)."""
        monkeypatch.setenv("PADDLE_TPU_SERVE_BULK", bulk)
        fmt, embed, head = _model()
        rng = np.random.RandomState(0)
        reqs = [(_prompt(rng, s), m)
                for s, m in [(5, 6), (3, 4), (7, 8), (4, 5), (6, 3)]]
        eng = ServingEngine(fmt, embed, head, num_slots=2,
                            max_seq_len=128, decode_chunk=2,
                            use_rotary=rotary)
        rids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
        eng.run()
        for (p, m), rid in zip(reqs, rids):
            want = sequential_tokens(fmt, embed, head, p, use_rotary=rotary,
                           max_new_tokens=m)
            np.testing.assert_array_equal(
                eng.results[rid]["tokens"], want)

    def test_per_slot_logit_controls_match_sequential(self):
        """eos / min_length / repetition_penalty are PER-SLOT data (no
        retrace): concurrent requests with different controls must each
        match their own sequential run."""
        fmt, embed, head = _model()
        rng = np.random.RandomState(1)
        reqs = [
            (_prompt(rng, 5), dict(max_new_tokens=10, eos_token_id=7,
                                   min_length=3)),
            (_prompt(rng, 4), dict(max_new_tokens=8, eos_token_id=2,
                                   repetition_penalty=1.5)),
            (_prompt(rng, 6), dict(max_new_tokens=6)),
            (_prompt(rng, 5), dict(max_new_tokens=12, eos_token_id=43)),
        ]
        eng = ServingEngine(fmt, embed, head, num_slots=2,
                            max_seq_len=128, decode_chunk=2,
                            enable_repetition_penalty=True)
        rids = [eng.submit(p, **kw) for p, kw in reqs]
        eng.run()
        for (p, kw), rid in zip(reqs, rids):
            want = sequential_tokens(fmt, embed, head, p, **kw)
            np.testing.assert_array_equal(
                eng.results[rid]["tokens"], want)

    def test_int8_cache_mode_parity(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_CACHE", "1")
        fmt, embed, head = _model()
        rng = np.random.RandomState(2)
        reqs = [(_prompt(rng, s), m) for s, m in [(5, 6), (3, 5)]]
        eng = ServingEngine(fmt, embed, head, num_slots=2,
                            max_seq_len=128, decode_chunk=2)
        rids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
        eng.run()
        for (p, m), rid in zip(reqs, rids):
            want = sequential_tokens(fmt, embed, head, p, max_new_tokens=m)
            np.testing.assert_array_equal(
                eng.results[rid]["tokens"], want)


class TestServingChurn:
    def test_slot_reuse_without_retrace(self):
        """The zero-recompile contract: after the warmup requests have
        exercised the engine's (bounded) executable set, 3 x num_slots
        more requests churning through freed slots must not trace
        anything new — admission/eviction is data, not structure."""
        fmt, embed, head = _model(seed=11)
        rng = np.random.RandomState(3)
        eng = ServingEngine(fmt, embed, head, num_slots=2,
                            max_seq_len=128, decode_chunk=2)
        # warmup: same shape-buckets the churn phase will use
        for _ in range(2):
            eng.submit(_prompt(rng, 5), max_new_tokens=6,
                       eos_token_id=7)
        eng.run()
        warm_traces = eng.metrics()["traces"]
        assert warm_traces > 0

        for _ in range(6):                    # 3 x num_slots
            eng.submit(_prompt(rng, 5), max_new_tokens=6,
                       eos_token_id=7)
        eng.run()
        m = eng.metrics()
        assert m["requests_admitted"] == 8
        assert m["requests_finished"] == 8
        assert m["traces"] == warm_traces, (
            f"slot churn retraced: {warm_traces} -> {m['traces']}")

    def test_submit_enforces_ring_capacity_invariant(self):
        """prompt + max_new_tokens > Smax could push cache_lens to Smax
        (the write kernels' documented invariant) — must refuse at
        submit, not corrupt at decode."""
        fmt, embed, head = _model(seed=12)
        eng = ServingEngine(fmt, embed, head, num_slots=1,
                            max_seq_len=128)
        with pytest.raises(ValueError, match="Smax"):
            eng.submit(np.ones(100, np.int32), max_new_tokens=29)
        # exactly at capacity is fine (cache_lens peaks at Smax - 1)
        rid = eng.submit(np.ones(4, np.int32), max_new_tokens=124)
        assert rid == 0

    def test_tokens_per_sec_zero_elapsed_guard(self):
        """A frozen clock leaves busy_s == 0.0 with tokens already
        emitted (e.g. a metrics() call after the first step under a
        coarse virtual clock): tokens_per_sec must read 0.0 — never a
        ZeroDivisionError, and never None once tokens exist."""
        fmt, embed, head = _model(seed=15)
        rng = np.random.RandomState(6)
        eng = ServingEngine(fmt, embed, head, num_slots=1,
                            max_seq_len=128, decode_chunk=2,
                            clock=lambda: 0.0)
        eng.submit(_prompt(rng, 4), max_new_tokens=3)
        # chunked admission may spend the first step(s) purely on
        # prefill — step until the first token lands (still under the
        # frozen clock, which is what the guard is about)
        while not eng.metrics()["tokens_emitted"]:
            eng.step()
        m = eng.metrics()
        assert m["tokens_emitted"] > 0
        assert m["busy_s"] == 0.0
        assert m["tokens_per_sec"] == 0.0
        # a truly idle engine still reports None (nothing to rate)
        fresh = ServingEngine(fmt, embed, head, num_slots=1,
                              max_seq_len=128)
        assert fresh.metrics()["tokens_per_sec"] is None

    def test_metrics_surface(self):
        fmt, embed, head = _model(seed=13)
        rng = np.random.RandomState(4)
        eng = ServingEngine(fmt, embed, head, num_slots=2,
                            max_seq_len=128, decode_chunk=2)
        eng.submit(_prompt(rng, 5), max_new_tokens=4)
        eng.submit(_prompt(rng, 3), max_new_tokens=6)
        eng.run()
        m = eng.metrics()
        assert m["tokens_emitted"] == 10
        assert m["requests_finished"] == 2
        assert m["tokens_per_sec"] > 0
        assert m["ttft_p50_s"] is not None and m["ttft_p50_s"] >= 0
        assert m["latency_p99_s"] >= m["ttft_p50_s"]
        # per-chunk records: occupancy/queue/step latency emitted every
        # chunk boundary
        assert eng.chunk_log
        rec = eng.chunk_log[0]
        for k in ("step_s", "new_tokens", "occupancy", "queue_depth",
                  "traces"):
            assert k in rec


class TestFullCacheGuard:
    """The decode_attention write kernels' cache_lens < Smax invariant:
    a full row must DROP the write (clamped to the last block), leaving
    the cache byte-identical — not address one block past the grid."""

    def test_fp_write_full_row_drops(self):
        from paddle_tpu.ops.pallas import decode_attention as da
        rng = np.random.RandomState(0)
        Lk, B, Hd, D, S = 2, 2, 4, 32, 128
        caches = jnp.asarray(rng.randn(Lk, 2, B, Hd, S, D), jnp.float32)
        q = jnp.asarray(rng.randn(B, Hd, 1, D), jnp.float32)
        kv = jnp.asarray(rng.randn(2, B, Hd, 1, D), jnp.float32)
        lens = jnp.asarray([S, 5], jnp.int32)      # row 0 is FULL
        c2, o = da.decode_attention_stacked_write(q, kv, caches, 0, lens)
        assert bool(jnp.isfinite(o).all())
        np.testing.assert_array_equal(np.asarray(c2[0, :, 0]),
                                      np.asarray(caches[0, :, 0]))
        # the non-full row still lands its write at position 5
        np.testing.assert_allclose(np.asarray(c2[0, 0, 1, :, 5, :]),
                                   np.asarray(kv[0, 1, :, 0, :]),
                                   rtol=1e-6)

    def test_i8_write_full_row_drops(self):
        from paddle_tpu.ops.pallas import decode_attention as da
        rng = np.random.RandomState(1)
        Lk, B, Hd, D, S = 2, 2, 4, 32, 128
        ci8 = jnp.ones((Lk, 2, B, Hd, S, D), jnp.int8)
        sc = jnp.ones((Lk, 2, B, Hd, 1, S), jnp.float32)
        q = jnp.asarray(rng.randn(B, Hd, 1, D), jnp.float32)
        kv = jnp.asarray(rng.randn(2, B, Hd, 1, D), jnp.float32)
        lens = jnp.asarray([S, 5], jnp.int32)
        c2, s2, o = da.decode_attention_stacked_i8_write(
            q, kv, ci8, sc, 0, lens)
        assert bool(jnp.isfinite(o).all())
        np.testing.assert_array_equal(np.asarray(c2[0, :, 0]),
                                      np.asarray(ci8[0, :, 0]))
        np.testing.assert_array_equal(np.asarray(s2[0, :, 0]),
                                      np.asarray(sc[0, :, 0]))
        # non-full row's int8 write landed
        assert not bool((c2[0, 0, 1, :, 5, :] ==
                         ci8[0, 0, 1, :, 5, :]).all())

    def test_engine_request_at_exact_capacity(self):
        """A request sized so its final write lands at Smax - 1 (the
        invariant's boundary) must complete cleanly."""
        fmt, embed, head = _model(seed=14)
        rng = np.random.RandomState(5)
        eng = ServingEngine(fmt, embed, head, num_slots=1,
                            max_seq_len=128, decode_chunk=2)
        p = _prompt(rng, 120)
        rid = eng.submit(p, max_new_tokens=8)
        eng.run()
        assert eng.results[rid]["tokens"].size == 8
        assert int(eng._lens[0]) == 127      # peaked at Smax - 1


class TestOverloadShedding:
    """Robustness satellites (ISSUE 3): bounded admission queue + per-
    request deadlines over the existing eviction machinery."""

    def test_max_pending_rejects_cleanly_then_drains(self):
        from paddle_tpu.inference.serving import AdmissionFull
        fmt, embed, head = _model(seed=21)
        rng = np.random.RandomState(0)
        eng = ServingEngine(fmt, embed, head, num_slots=2,
                            max_seq_len=128, decode_chunk=2,
                            max_pending=3)
        for _ in range(3):
            eng.submit(_prompt(rng, 4), max_new_tokens=3)
        with pytest.raises(AdmissionFull):
            eng.submit(_prompt(rng, 4), max_new_tokens=3)
        assert eng.metrics()["requests_rejected"] == 1
        eng.run()                        # shed != broken: queue drains
        assert eng.metrics()["requests_finished"] == 3
        # capacity freed -> admission works again
        rid = eng.submit(_prompt(rng, 4), max_new_tokens=2)
        eng.run()
        assert eng.results[rid]["tokens"].size == 2

    def test_deadline_evicts_queued_and_running(self):
        fmt, embed, head = _model(seed=22)
        rng = np.random.RandomState(1)
        clk = [0.0]
        eng = ServingEngine(fmt, embed, head, num_slots=1,
                            max_seq_len=128, decode_chunk=2,
                            clock=lambda: clk[0])
        rid_run = eng.submit(_prompt(rng, 4), max_new_tokens=60,
                             deadline_s=5.0)
        rid_q = eng.submit(_prompt(rng, 4), max_new_tokens=4,
                           deadline_s=1.0)
        eng.step()                       # admits rid_run; rid_q queued
        assert eng.results == {}
        clk[0] = 2.0
        eng.step()                       # rid_q shed from the queue
        assert eng.results[rid_q]["expired"] is True
        assert eng.results[rid_q]["tokens"].size == 0
        clk[0] = 6.0
        eng.step()                       # rid_run evicted mid-decode
        assert eng.results[rid_run]["expired"] is True
        assert not eng._active.any()
        assert eng.metrics()["requests_expired"] == 2
        # the evicted slot is reusable: a fresh request completes
        rid3 = eng.submit(_prompt(rng, 5), max_new_tokens=3)
        eng.run()
        assert eng.results[rid3]["expired"] is False
        assert eng.results[rid3]["tokens"].size == 3
        # expired requests are shed, not finished: they stay out of the
        # finished count and the latency percentiles
        m = eng.metrics()
        assert m["requests_finished"] == 1
        assert m["requests_expired"] == 2

    def test_reset_metrics_zeroes_shed_counters(self):
        """reset_metrics() must zero rejected/expired alongside admitted,
        or a post-warmup shed-rate computed from one metrics() snapshot
        mixes windows."""
        from paddle_tpu.inference.serving import AdmissionFull
        fmt, embed, head = _model(seed=24)
        rng = np.random.RandomState(3)
        eng = ServingEngine(fmt, embed, head, num_slots=2,
                            max_seq_len=128, decode_chunk=2,
                            max_pending=1)
        eng.submit(_prompt(rng, 4), max_new_tokens=2)
        with pytest.raises(AdmissionFull):
            eng.submit(_prompt(rng, 4), max_new_tokens=2)
        eng.run()
        assert eng.metrics()["requests_rejected"] == 1
        eng.reset_metrics()
        m = eng.metrics()
        assert m["requests_admitted"] == 0
        assert m["requests_rejected"] == 0
        assert m["requests_expired"] == 0

    def test_no_deadline_is_unbounded(self):
        fmt, embed, head = _model(seed=23)
        rng = np.random.RandomState(2)
        clk = [0.0]
        eng = ServingEngine(fmt, embed, head, num_slots=1,
                            max_seq_len=128, decode_chunk=2,
                            clock=lambda: clk[0])
        rid = eng.submit(_prompt(rng, 4), max_new_tokens=4)
        clk[0] = 1e6                     # ancient request, no deadline
        eng.run()
        assert eng.results[rid]["expired"] is False
        assert eng.results[rid]["tokens"].size == 4


class TestPrefixCacheServing:
    """Prefix-cache KV reuse inside the engine (ISSUE 4): deterministic
    on/off parity across admission/eviction churn, zero retraces after
    warmup with caching enabled, the one-knob prefill/block ladder, and
    the full-counter metrics reset."""

    def _shared_reqs(self, rng, n=12, n_prefixes=3):
        prefixes = [_prompt(rng, 8) for _ in range(n_prefixes)]
        # lead with an exactly-block-aligned prompt twice: the repeat is
        # a FULLY-cached prompt, whose final block must be dropped so
        # the first-token sample still has a suffix token — and its
        # 1-block adopt ladder bucket compiles up front (warmup must
        # exercise every K bucket the churn phase will reuse)
        reqs = [(prefixes[0].copy(), 3), (prefixes[0].copy(), 3)]
        for i in range(n):
            sfx = _prompt(rng, 2 + i % 5)
            reqs.append((np.concatenate([prefixes[i % n_prefixes], sfx]),
                         4))
        return reqs

    @pytest.mark.parametrize("sample", [False, True])
    def test_on_off_parity_across_eviction_churn(self, sample,
                                                 serving_metrics_ok):
        """Enabling the prefix cache must never change sampled outputs —
        even with a pool so small (3 blocks vs 2-block prefixes) that
        admission constantly evicts and republishes blocks."""
        fmt, embed, head = _model(seed=31)
        rng = np.random.RandomState(5)
        reqs = self._shared_reqs(rng)

        def run(blocks):
            paddle.seed(0)               # identical sampling key stream
            eng = ServingEngine(fmt, embed, head, num_slots=2,
                                max_seq_len=128, decode_chunk=2,
                                prefill_cap=4, prefix_cache_blocks=blocks,
                                do_sample=sample, top_k=5)
            rids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
            eng.run()
            return eng, [eng.results[r]["tokens"] for r in rids]

        eng_on, toks_on = run(3)
        eng_off, toks_off = run(0)
        for a, b in zip(toks_on, toks_off):
            np.testing.assert_array_equal(a, b)
        m = serving_metrics_ok(eng_on)
        serving_metrics_ok(eng_off)
        assert m["prefix_hits"] > 0                 # reuse really happened
        assert m["prefill_tokens_saved"] > 0
        assert m["prefix_store"]["evictions"] > 0   # ... under churn

    def test_zero_retraces_after_warmup_with_cache(self,
                                                   serving_metrics_ok):
        """The adopt/commit copy paths ride the same bounded pow-2
        executable ladders as prefill: once warmup has exercised the
        buckets, shared-prefix churn must not trace anything new."""
        fmt, embed, head = _model(seed=32)
        rng = np.random.RandomState(6)
        reqs = self._shared_reqs(rng)
        eng = ServingEngine(fmt, embed, head, num_slots=2,
                            max_seq_len=128, decode_chunk=2,
                            prefill_cap=4, prefix_cache_blocks=16)
        for p, m in reqs[:7]:
            eng.submit(p, max_new_tokens=m)
        eng.run()
        warm = eng.metrics()["traces"]
        assert warm > 0
        for p, m in reqs[7:]:
            eng.submit(p, max_new_tokens=m)
        eng.run()
        m = serving_metrics_ok(eng)
        assert m["traces"] == warm, (
            f"prefix-cache churn retraced: {warm} -> {m['traces']}")
        assert m["prefix_hits"] > 0

    def test_prefill_cap_knob_and_validation(self, monkeypatch):
        """prefill_cap is the ONE knob for the prefill chunk ladder and
        the prefix block size: constructor arg, env default, pow-2
        validated."""
        fmt, embed, head = _model(seed=33)
        with pytest.raises(ValueError, match="power of two"):
            ServingEngine(fmt, embed, head, num_slots=1, max_seq_len=128,
                          prefill_cap=24)
        monkeypatch.setenv("PADDLE_SERVING_PREFILL_CAP", "12")
        with pytest.raises(ValueError, match="power of two"):
            ServingEngine(fmt, embed, head, num_slots=1, max_seq_len=128)
        monkeypatch.setenv("PADDLE_SERVING_PREFILL_CAP", "8")
        eng = ServingEngine(fmt, embed, head, num_slots=1,
                            max_seq_len=128, prefix_cache_blocks=4)
        assert eng.prefill_cap == 8
        assert eng.prefix_cache.block_tokens == 8       # ladders aligned
        assert eng._prefill_chunks(20) == [8, 8, 4]
        # explicit arg wins over env
        eng2 = ServingEngine(fmt, embed, head, num_slots=1,
                             max_seq_len=128, prefill_cap=16)
        assert eng2.prefill_cap == 16

    def test_reset_metrics_zeroes_every_counter(self):
        """PR 3 missed requests_rejected/expired on the first pass; this
        pins the FULL surface: after reset_metrics(keep_results=False),
        every metrics() key except the trace spy (documented: never
        reset) and the store-lifetime prefix_store stats must read
        exactly like a fresh engine's."""
        fmt, embed, head = _model(seed=34)
        rng = np.random.RandomState(7)
        eng = ServingEngine(fmt, embed, head, num_slots=2,
                            max_seq_len=128, decode_chunk=2,
                            prefill_cap=4, prefix_cache_blocks=8)
        fresh = eng.metrics()
        for _ in range(3):
            eng.submit(_prompt(rng, 9), max_new_tokens=3)
        eng.run()
        m = eng.metrics()
        moved = [k for k in fresh
                 if k != "prefix_store" and m[k] != fresh[k]]
        assert "prefix_hits" in moved or "prefix_misses" in moved
        assert "prefill_tokens_computed" in moved
        eng.reset_metrics(keep_results=False)
        after = eng.metrics()
        for k in fresh:
            if k in ("traces", "prefix_store", "kv_blocks_total",
                     "kv_blocks_used", "kv_blocks_free"):
                # allocator STATE, not window counters: published
                # prefix blocks legitimately stay resident across a
                # metrics reset (like the trace spy and store stats)
                continue
            assert after[k] == fresh[k], (
                f"reset_metrics missed {k}: {after[k]!r} != fresh "
                f"{fresh[k]!r}")
