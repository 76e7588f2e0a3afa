"""Benchmark: all five BASELINE configs on one TPU chip.

Prints ONE JSON line. The top-level fields are the headline config
(GPT-2 124M train tokens/s/chip); the other four BASELINE configs (BERT
DP+AMP-O2+stage2, LLaMA-proxy mp·pp·stage3, ViT-L/16, ERNIE-MoE EP) ride
in the "configs" array of the same line, each with its own
metric/value/unit. Per-config progress goes to stderr.

Runs on a TPU or not at all (paddle_tpu.device.chip.require_tpu): there
is no CPU fallback, and a config that raises ends the run non-zero.
ROADMAP.md S1 replaces this script with a cell-driven benchmark.

Time-budgeted BETWEEN configs: BENCH_BUDGET_S (default 1500) gates
whether each extra config STARTS (per-config cost estimates); a started
config runs to completion, so a caller's timeout should budget
BENCH_BUDGET_S plus one config overrun.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_T0 = time.monotonic()


def _timed_steps(step_fn, fetch_loss, steps):
    """Median per-step seconds over chained chunks, each ended by a
    device→host fetch of the loss (which waits for the chunk's work)."""
    chunk = max(1, steps // 5)
    times = []
    final_loss = None
    done = 0
    while done < steps:
        n = min(chunk, steps - done)
        t0 = time.perf_counter()
        for _ in range(n):
            out = step_fn()
        final_loss = fetch_loss(out)
        times.append((time.perf_counter() - t0) / n)
        done += n
    return float(np.median(times)), final_loss


def _budget_left(budget_s):
    return budget_s - (time.monotonic() - _T0)


def _warm(train_step, args, n):
    """Warmup calls, ended by a host fetch of the loss. A warmup
    exception propagates: a config that raises ends the run."""
    for _ in range(n):
        loss = train_step(*args)
    float(np.asarray(loss._data))


_BUDGET_S = [1500.0]   # set by main(); scan gating reads it


def _timed_train(train_step, args, make_stacked, steps, scan_k):
    """Median per-step seconds for a compiled train step, scan-amortized
    when scan_k > 0 (k steps per device program via run_steps).
    make_stacked() builds the [k, ...]-stacked per-step batches — called
    only on the scan path so BENCH_SCAN=0 A/B runs don't upload unused
    device buffers. Returns (med_s, loss).

    The scan wrapper costs a SECOND compile. If the remaining budget
    can't absorb that, fall back to plain per-step timing: a slightly
    worse number for this config beats starving the configs after it.
    Returns (med_s, loss, effective_scan_k) — callers MUST record the
    returned scan_k, not the requested one, so per-dispatch fallback
    runs are distinguishable in the JSON."""
    if scan_k > 0 and _budget_left(_BUDGET_S[0]) < 300:
        print("bench: scan skipped (budget) — per-dispatch timing",
              file=sys.stderr)
        scan_k = 0
    if scan_k > 0:
        stacked_args = make_stacked()
        out = train_step.run_steps(scan_k, *stacked_args)  # compile + warm
        float(np.asarray(out._data[-1]))
        med_chunk, loss = _timed_steps(
            lambda: train_step.run_steps(scan_k, *stacked_args),
            lambda o: float(np.asarray(o._data[-1])),
            max(steps // scan_k, 3))
        return med_chunk / scan_k, loss, scan_k
    med, loss = _timed_steps(lambda: train_step(*args),
                             lambda out: float(np.asarray(out._data)), steps)
    return med, loss, 0


# --------------------------------------------------------------------------
# configs[0] — GPT-2 124M single-chip train (headline)
# --------------------------------------------------------------------------

def bench_gpt2(peak_tflops):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt2_124m

    batch = int(os.environ.get("BENCH_BATCH", "8"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))

    paddle.seed(0)
    model = gpt2_124m()
    vocab = min(model.config.vocab_size, 50000)  # real-token range (pad
    # rows above 50256 are never sampled)
    model.bfloat16()  # bf16 params; fp32 master weights in AdamW
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    n_params = sum(p.size for p in model.parameters())

    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq + 1)).astype(np.int32)
    x = paddle.to_tensor(ids[:, :-1])
    y = paddle.to_tensor(ids[:, 1:])

    def _step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # to_static at its default: the state is donated
    train_step = paddle.jit.to_static(_step)

    # First call traces with slot creation (state superset), second call
    # recompiles into the steady signature — no eager per-op compile storm.
    _warm(train_step, (x, y), warmup)

    # default: 8 steps per device program (lax.scan over the step), so
    # the host dispatches once per 8 steps. Distinct batches per step,
    # stacked on a [k, ...] leading axis.
    scan_k = int(os.environ.get("BENCH_SCAN", "8"))

    def make_stacked():
        sids = rng.randint(0, vocab,
                           (scan_k, batch, seq + 1)).astype(np.int32)
        return (paddle.to_tensor(sids[:, :, :-1]),
                paddle.to_tensor(sids[:, :, 1:]))
    med, final_loss, scan_k = _timed_train(train_step, (x, y),
                                           make_stacked, steps, scan_k)
    tokens_per_sec = batch * seq / med

    cfg = model.config
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * seq
    mfu = (flops_per_token * tokens_per_sec) / (peak_tflops * 1e12)

    return {
        "metric": "gpt2_124m_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "mfu": round(mfu, 4),
        "median_step_s": round(med, 5),
        "batch": batch, "seq": seq, "params": n_params,
        "loss": final_loss,
        "donated": train_step._donate_state,
        "warmup": warmup,   # methodology field: r4 default drops 5 -> 3
        **({"scan_steps": scan_k} if scan_k > 0 else {}),
    }


# --------------------------------------------------------------------------
# configs[1] — BERT-base pretrain, DP + AMP-O2 + GroupSharded stage2
# --------------------------------------------------------------------------

def bench_bert(peak_tflops):
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertForPretraining, bert_base
    from paddle_tpu.distributed.sharding import group_sharded_parallel

    batch = int(os.environ.get("BENCH_BERT_BATCH", "16"))
    seq = int(os.environ.get("BENCH_BERT_SEQ", "512"))
    steps = 10

    paddle.seed(0)
    # vocab padded 30522 -> 30720 (240x128): MXU lane alignment for the
    # MLM decoder matmul, same trick as GPT-2's 50304 default; ids and
    # labels are sampled from the REAL 30522 vocab below so no token or
    # MLM target ever indexes the 198 pad slots (MFU still counts the pad
    # rows — they are multiplied whether or not they are ever the target)
    model = BertForPretraining(bert_base(vocab_size=30720))
    vocab = 30522
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    # AMP-O2: bf16 params + fp32 master weights (the reference's fp16-O2
    # on TPU hardware terms), stage-2 = optimizer+grad sharding specs
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16")
    if os.environ.get("BENCH_BERT_PLAIN") != "1":
        # BENCH_BERT_PLAIN=1: drop the stage-2 wrapper (keep AMP-O2) —
        # isolates what the sharding machinery costs at world=1
        model, opt, _ = group_sharded_parallel(model, opt, level="os_g")
    n_params = sum(p.size for p in model.parameters())

    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    labels = ids.copy()
    labels[rng.rand(*labels.shape) > 0.15] = -100  # MLM: 15% predicted
    x = paddle.to_tensor(ids)
    y = paddle.to_tensor(labels)
    nsp = paddle.to_tensor(rng.randint(0, 2, (batch,)).astype(np.int32))

    def _step(x, y, nsp):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = model(x, masked_lm_labels=y, next_sentence_labels=nsp)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step = paddle.jit.to_static(_step)
    _warm(train_step, (x, y, nsp), 3)

    scan_k = int(os.environ.get("BENCH_SCAN", "8"))

    def make_stacked():
        sids = rng.randint(0, vocab, (scan_k, batch, seq)).astype(np.int32)
        slabels = sids.copy()
        slabels[rng.rand(*slabels.shape) > 0.15] = -100
        return (paddle.to_tensor(sids), paddle.to_tensor(slabels),
                paddle.to_tensor(rng.randint(
                    0, 2, (scan_k, batch)).astype(np.int32)))
    med, final_loss, scan_k = _timed_train(train_step, (x, y, nsp),
                                           make_stacked, steps, scan_k)
    tokens_per_sec = batch * seq / med
    mfu = (6 * n_params * tokens_per_sec) / (peak_tflops * 1e12)
    return {
        "metric": "bert_base_amp_o2_stage2_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2), "unit": "tokens/s",
        "mfu": round(mfu, 4), "median_step_s": round(med, 5),
        "batch": batch, "seq": seq, "params": n_params,
        "loss": final_loss,
    }


# --------------------------------------------------------------------------
# configs[2] — LLaMA proxy under Fleet hybrid mp·pp·stage3 (single-chip
# degrees collapse to 1; the 8-device composition is proven by
# dryrun_multichip phase 5 + tests/test_hybrid_composition.py)
# --------------------------------------------------------------------------

def bench_llama(peak_tflops):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.distributed.sharding import group_sharded_parallel

    # ~350M proxy of the 7B architecture, scaled to one v5e chip
    c = LlamaConfig(vocab_size=32000, hidden_size=1024, num_layers=16,
                    num_heads=16, intermediate_size=2816,
                    max_position=1024)
    batch, seq, steps = 8, 1024, 10

    paddle.seed(0)
    model = LlamaForCausalLM(c)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    if os.environ.get("BENCH_LLAMA_PLAIN") != "1":
        # BENCH_LLAMA_PLAIN=1: drop the stage-3 wrapper — isolates what
        # param/grad resharding costs at world=1 (llama's MFU laggard
        # hunt; the 8-dev composition is proven by dryrun_multichip)
        model, opt, _ = group_sharded_parallel(model, opt, level="p_g_os")
    n_params = sum(p.size for p in model.parameters())

    rng = np.random.RandomState(0)
    ids = rng.randint(0, c.vocab_size, (batch, seq + 1)).astype(np.int32)
    x = paddle.to_tensor(ids[:, :-1])
    y = paddle.to_tensor(ids[:, 1:])

    def _step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step = paddle.jit.to_static(_step)
    _warm(train_step, (x, y), 3)

    scan_k = int(os.environ.get("BENCH_SCAN", "8"))

    def make_stacked():
        sids = rng.randint(0, c.vocab_size,
                           (scan_k, batch, seq + 1)).astype(np.int32)
        return (paddle.to_tensor(sids[:, :, :-1]),
                paddle.to_tensor(sids[:, :, 1:]))
    med, final_loss, scan_k = _timed_train(train_step, (x, y),
                                           make_stacked, steps, scan_k)
    tokens_per_sec = batch * seq / med
    flops_per_token = 6 * n_params + 12 * c.num_layers * c.hidden_size * seq
    mfu = (flops_per_token * tokens_per_sec) / (peak_tflops * 1e12)
    return {
        "metric": "llama_proxy_stage3_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2), "unit": "tokens/s",
        "mfu": round(mfu, 4), "median_step_s": round(med, 5),
        "batch": batch, "seq": seq, "params": n_params,
        "loss": final_loss,
    }


# --------------------------------------------------------------------------
# configs[3] — ViT-L/16 ImageNet-shaped classification train
# --------------------------------------------------------------------------

def bench_vit(peak_tflops):
    import paddle_tpu as paddle
    from paddle_tpu.models.vit import vit_l_16

    paddle.seed(0)   # BEFORE model build: initializers draw from the key
    # recompute: ViT-L b32 saved-residuals OOMed a 16 GB chip twice
    # (2026-07) — remat the 24 blocks, trading ~1/3 extra FLOPs for O(1)
    # per-block activation memory. BENCH_VIT_REMAT: "1" every block
    # (default), N>=2 every Nth block, "0" none — the granular-remat
    # A/B. int semantics match ViT.forward exactly: 0 = none, 1 = every
    # block, N>=2 = every Nth block
    model = vit_l_16(
        recompute=int(os.environ.get("BENCH_VIT_REMAT", "1")))
    batch, size, steps = int(os.environ.get("BENCH_VIT_BATCH", "32")), \
        224, 10

    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    n_params = sum(p.size for p in model.parameters())

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, 3, size, size).astype(np.float32))
    x = x.astype("bfloat16")   # match the bf16 params: conv on the MXU
    y = paddle.to_tensor(rng.randint(
        0, 10, (batch,)).astype(np.int32))

    def _step(x, y):
        logits = model(x)
        loss = paddle.nn.functional.cross_entropy(logits, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step = paddle.jit.to_static(_step)
    _warm(train_step, (x, y), 3)

    # scan capped at 4: the stacked image batches are the one large input
    # ([k, B, 3, 224, 224]); k=8 would hold ~150 MB of inputs resident
    scan_k = min(int(os.environ.get("BENCH_SCAN", "4")), 4)

    def make_stacked():
        sx = rng.randn(scan_k, batch, 3, size, size).astype(np.float32)
        xs = paddle.to_tensor(sx).astype("bfloat16")
        return (xs, paddle.to_tensor(
            rng.randint(0, 10, (scan_k, batch)).astype(np.int32)))
    med, final_loss, scan_k = _timed_train(train_step, (x, y),
                                           make_stacked, steps, scan_k)
    images_per_sec = batch / med
    # ViT-L/16 fwd ≈ 61 GFLOPs/image at 224², train ≈ 3×
    flops_per_image = 61e9 * 3
    mfu = (flops_per_image * images_per_sec) / (peak_tflops * 1e12)
    return {
        "metric": "vit_l16_train_images_per_sec_per_chip",
        "value": round(images_per_sec, 2), "unit": "images/s",
        "mfu": round(mfu, 4), "median_step_s": round(med, 5),
        "batch": batch, "image_size": size, "params": n_params,
        "loss": final_loss,
    }


# --------------------------------------------------------------------------
# configs[4] — ERNIE-MoE expert-parallel train step
# --------------------------------------------------------------------------

def bench_moe(peak_tflops):
    import paddle_tpu as paddle
    from paddle_tpu.models.moe import ErnieMoEConfig, ErnieMoEForCausalLM

    c = ErnieMoEConfig(vocab_size=30000, hidden_size=768, num_layers=6,
                       num_heads=12, intermediate_size=3072,
                       num_experts=8, max_position=1024, dropout=0.0)
    batch, seq, steps = 8, 512, 10

    paddle.seed(0)
    model = ErnieMoEForCausalLM(c)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    n_params = sum(p.size for p in model.parameters())

    rng = np.random.RandomState(0)
    ids = rng.randint(0, c.vocab_size, (batch, seq + 1)).astype(np.int32)
    x = paddle.to_tensor(ids[:, :-1])
    y = paddle.to_tensor(ids[:, 1:])

    def _step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step = paddle.jit.to_static(_step)
    _warm(train_step, (x, y), 3)

    scan_k = int(os.environ.get("BENCH_SCAN", "8"))

    def make_stacked():
        sids = rng.randint(0, c.vocab_size,
                           (scan_k, batch, seq + 1)).astype(np.int32)
        return (paddle.to_tensor(sids[:, :, :-1]),
                paddle.to_tensor(sids[:, :, 1:]))
    med, final_loss, scan_k = _timed_train(train_step, (x, y),
                                           make_stacked, steps, scan_k)
    tokens_per_sec = batch * seq / med

    # MFU from the COMPUTED flops (capacity-padded expert compute, the
    # flops the chip actually runs): per token fwd = attn block matmuls
    # + dense-FFN layers + (E·C/S)-weighted expert FFN + tied LM head.
    e_dim, i_dim = c.hidden_size, c.intermediate_size
    # the gate's own capacity rule — not a re-derivation that could drift
    cap = next(blk.ffn.gate for blk in model.blocks
               if blk.use_moe).capacity(seq)
    n_moe = sum(1 for i in range(c.num_layers)
                if i % c.moe_every == c.moe_every - 1)
    n_dense = c.num_layers - n_moe
    per_tok_fwd = (
        c.num_layers * (8 * e_dim * e_dim + 4 * seq * e_dim)   # attn+proj
        + n_dense * 4 * e_dim * i_dim                          # dense FFN
        + n_moe * (c.num_experts * cap / seq) * 4 * e_dim * i_dim
        + 2 * e_dim * c.vocab_size)                            # LM head
    mfu = (3 * per_tok_fwd * tokens_per_sec) / (peak_tflops * 1e12)

    # decomposition (BASELINE configs[4]'s real metric): identity-dispatch
    # twin keeps the expert compute identical but removes gate + dispatch/
    # combine einsums (the alltoall path under EP) — the delta IS the
    # dispatch cost. BOTH sides of the subtraction are timed PER-DISPATCH
    # (the main `med` above is scan-amortized; subtracting a per-dispatch
    # twin from it would fold the host dispatch cost into the delta and
    # could even go negative). Two extra timings; gated on remaining
    # budget.
    dispatch_ms = None
    dispatch_raw_ms = None
    noise_floor_ms = None
    if _budget_left(_BUDGET_S[0]) > 300:
        try:
            med_plain, _ = _timed_steps(          # real step, per-dispatch
                lambda: train_step(x, y),
                lambda out: float(np.asarray(out._data)),
                max(steps // 2, 2))
            os.environ["PADDLE_TPU_MOE_IDENTITY_DISPATCH"] = "1"
            twin_step = paddle.jit.to_static(_step, donate_state=False)
            _warm(twin_step, (x, y), 2)
            med_twin, _ = _timed_steps(
                lambda: twin_step(x, y),
                lambda out: float(np.asarray(out._data)),
                max(steps // 2, 2))
            # repeat the plain side (already compiled, cheap): the spread
            # between its two medians is the run-to-run noise floor; a
            # delta below the floor is indistinguishable from noise and
            # must not be published as a (let alone negative) cost.
            med_plain2, _ = _timed_steps(
                lambda: train_step(x, y),
                lambda out: float(np.asarray(out._data)),
                max(steps // 2, 2))
            noise_floor_ms = round(abs(med_plain - med_plain2) * 1000, 3)
            raw = (med_plain + med_plain2) / 2 - med_twin
            dispatch_raw_ms = round(raw * 1000, 3)
            dispatch_ms = (dispatch_raw_ms
                           if dispatch_raw_ms > noise_floor_ms else 0.0)
        finally:
            os.environ.pop("PADDLE_TPU_MOE_IDENTITY_DISPATCH", None)

    rec = {
        "metric": "ernie_moe_ep_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2), "unit": "tokens/s",
        "mfu": round(mfu, 4),
        "median_step_s": round(med, 5),
        "batch": batch, "seq": seq, "params": n_params,
        "num_experts": c.num_experts, "loss": final_loss,
    }
    if dispatch_ms is not None:
        rec["gate_dispatch_combine_ms"] = dispatch_ms
        rec["gate_dispatch_combine_raw_ms"] = dispatch_raw_ms
        rec["dispatch_noise_floor_ms"] = noise_floor_ms
        rec["expert_compute_step_ms"] = round(med_twin * 1000, 3)
    return rec


# --------------------------------------------------------------------------

def main():
    from paddle_tpu.device.chip import (device_record, peak_rates,
                                        release_device_memory, require_tpu,
                                        use_compile_cache)
    dev = require_tpu()
    use_compile_cache()
    peak_tflops = peak_rates(dev.device_kind)["bf16_tflops"]
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "1500"))
    _BUDGET_S[0] = budget_s

    only = os.environ.get("BENCH_ONLY")
    if only and os.environ.get("BENCH_HEADLINE", "1") == "0":
        # sweep runs measuring ONE extra config (e.g. BENCH_ONLY=vit)
        # shouldn't pay the gpt2 headline as overhead; only honored in
        # BENCH_ONLY mode so the full run always measures its headline
        headline = {"metric": "gpt2_124m_train_tokens_per_sec_per_chip",
                    "value": None, "skipped": "BENCH_HEADLINE=0"}
        print("bench: gpt2 headline skipped (BENCH_HEADLINE=0)",
              file=sys.stderr)
    else:
        headline = bench_gpt2(peak_tflops)
        print(f"bench: gpt2 done {headline['value']} tok/s "
              f"(mfu {headline['mfu']})", file=sys.stderr)

    # (name, fn, stable metric key, rough compile+run cost estimate in s —
    # a config only STARTS if the estimate fits the remaining budget; a
    # started config runs to completion)
    extra_benches = [
        ("llama", bench_llama,
         "llama_proxy_stage3_tokens_per_sec_per_chip", 300),
        ("vit", bench_vit, "vit_l16_train_images_per_sec_per_chip", 300),
        ("moe", bench_moe, "ernie_moe_ep_tokens_per_sec_per_chip", 240),
        ("bert", bench_bert,
         "bert_base_amp_o2_stage2_tokens_per_sec_per_chip", 300),
    ]
    if only:
        # tuning-sweep mode: skip the other extras so each sweep point
        # costs one compile+run
        extra_benches = [e for e in extra_benches if e[0] == only]
    skip = {s for s in os.environ.get("BENCH_SKIP", "").split(",") if s}
    extra_benches = [e for e in extra_benches if e[0] not in skip]
    configs = []
    for name, fn, metric_key, est_s in extra_benches:
        left = _budget_left(budget_s)
        if left < est_s:
            configs.append({"metric": metric_key, "skipped": "time budget",
                            "budget_left_s": round(left, 1)})
            print(f"bench: {name} skipped (budget)", file=sys.stderr)
            continue
        # each config rebuilds all state from a seed, so nothing of the
        # previous one has to stay on the device. A config that raises
        # ends the run non-zero: no retry, no "error" row.
        n = release_device_memory()
        print(f"bench: released {n} live device arrays", file=sys.stderr)
        rec = fn(peak_tflops)
        configs.append(rec)
        print(f"bench: {name} done {rec.get('value')} {rec.get('unit')}",
              file=sys.stderr)

    record = dict(headline)
    record["device"] = device_record(dev)
    record["configs"] = configs
    _emit_record(record)


def _emit_record(record):
    """stdout gets ONE compact, bounded JSON line (a caller may keep only
    a bounded tail of the output); the full record goes to
    BENCH_RESULT.json."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_RESULT.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    compact = {k: record[k] for k in
               ("metric", "value", "unit", "mfu", "device")
               if k in record}
    compact["full_record"] = "BENCH_RESULT.json"
    print(json.dumps(compact))


if __name__ == "__main__":
    main()
