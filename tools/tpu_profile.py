"""Per-op time breakdown of the headline GPT-2 train step.

Runs the bench-identical step under jax.profiler.trace and aggregates the
device-track op durations from the perfetto JSON the profiler writes, so
kernel work (matmul fusions, attention, copies, collectives) can be ranked
by per-step cost; ablation timing (variants of the step with parts removed)
is the second mode. Runs on a TPU or not at all (through the chip tool).

Usage:  python tools/tpu_profile.py [outdir]   (default chiprun_out/tpu_profile)
Env:    PROF_STEPS (default 10), PROF_MODE=trace|ablate|both (default both),
        PROF_MODEL=gpt2|tiny|bert|llama (default gpt2),
        BENCH_BATCH/BENCH_SEQ, BENCH_BERT_BATCH/BENCH_BERT_SEQ as in
        bench.py.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import sys
import time
from collections import defaultdict

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_parts():
    """Bench-identical pieces for PROF_MODEL ∈ {gpt2 (default), tiny,
    bert, llama}, shared by the trace and ablate modes:
    (model, opt, args, loss_call, body_call, tokens_per_step) where
    loss_call(*args) returns the full loss (heads + CE) and
    body_call(*args) a scalar over the backbone only (no heads/CE)."""
    import paddle_tpu as paddle

    target = os.environ.get("PROF_MODEL", "gpt2")
    paddle.seed(0)
    rng = np.random.RandomState(0)
    if target == "bert":
        from paddle_tpu.models.bert import BertForPretraining, bert_base
        from paddle_tpu.distributed.sharding import group_sharded_parallel
        batch = int(os.environ.get("BENCH_BERT_BATCH", "16"))
        seq = int(os.environ.get("BENCH_BERT_SEQ", "512"))
        # bench-identical: vocab padded 30522 -> 30720 (240x128 MXU
        # lanes) with ids sampled from the REAL vocab (bench.py bert)
        model = BertForPretraining(bert_base(vocab_size=30720))
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")
        model, opt, _ = group_sharded_parallel(model, opt, level="os_g")
        ids = rng.randint(0, 30522, (batch, seq)).astype(np.int32)
        labels = ids.copy()
        labels[rng.rand(*labels.shape) > 0.15] = -100
        args = (paddle.to_tensor(ids), paddle.to_tensor(labels),
                paddle.to_tensor(rng.randint(0, 2, (batch,)).astype(np.int32)))

        def loss_call(x, y, nsp):
            with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
                return model(x, masked_lm_labels=y,
                             next_sentence_labels=nsp)

        def body_call(x, y, nsp):
            inner = getattr(model, "_layers", model)
            with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
                seq_out, _pooled = inner.bert(x)
            return seq_out.sum()
    elif target == "vit":
        # bench-identical ViT-L/16 (bench.py bench_vit): b32x224 bf16,
        # granular remat via BENCH_VIT_REMAT, AdamW fp32 masters
        from paddle_tpu.models.vit import vit_l_16
        batch = int(os.environ.get("BENCH_VIT_BATCH", "32"))
        seq = 224
        model = vit_l_16(
            recompute=int(os.environ.get("BENCH_VIT_REMAT", "1")))
        model.bfloat16()
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     multi_precision=True)
        x_np = rng.randn(batch, 3, seq, seq).astype(np.float32)
        y_np = rng.randint(0, 1000, (batch,)).astype(np.int32)
        args = (paddle.to_tensor(x_np), paddle.to_tensor(y_np))

        def loss_call(x, y):
            import paddle_tpu.nn.functional as F
            return F.cross_entropy(model(x), y)

        def body_call(x, y):
            # backbone without the classifier head/CE: reuse the model's
            # own forward with the head detached is invasive; the head is
            # one [D, 1000] matmul — time it via fwd minus fwd_no_head
            head = model.head
            model.head = None
            try:
                out = model(x)
            finally:
                model.head = head
            return out.sum()

        # tokens/step analogue: patches per image
        return model, opt, args, loss_call, body_call, batch * 197
    else:
        if target == "llama":
            from paddle_tpu.models.llama import (LlamaConfig,
                                                 LlamaForCausalLM)
            c = LlamaConfig(vocab_size=32000, hidden_size=1024,
                            num_layers=16, num_heads=16,
                            intermediate_size=2816, max_position=1024)
            batch, seq = 8, 1024
            model = LlamaForCausalLM(c)
            vocab = c.vocab_size
            body = "llama"
            stage3 = True
        else:
            from paddle_tpu.models.gpt import gpt2_124m, gpt2_tiny
            batch = int(os.environ.get("BENCH_BATCH", "8"))
            seq = int(os.environ.get("BENCH_SEQ", "1024"))
            model = gpt2_tiny() if target == "tiny" else gpt2_124m()
            # bench-identical id range; gpt2_tiny's vocab is far smaller
            # than 50000 and out-of-range ids profile a clamped workload
            vocab = min(model.config.vocab_size, 50000)
            body = "gpt"
            stage3 = False
        model.bfloat16()
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     multi_precision=True)
        if stage3:
            # bench-identical: bench_llama wraps stage-3 sharding (1-dev
            # collapse on a single chip, but step() goes through the
            # sharded optimizer path being profiled)
            from paddle_tpu.distributed.sharding import (
                group_sharded_parallel)
            model, opt, _ = group_sharded_parallel(model, opt,
                                                   level="p_g_os")
        ids = rng.randint(0, vocab, (batch, seq + 1)).astype(np.int32)
        args = (paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:]))

        def loss_call(x, y):
            return model(x, labels=y)

        def body_call(x, y):
            inner = getattr(model, "_layers", model)
            return getattr(inner, body)(x).sum()

    return model, opt, args, loss_call, body_call, batch * seq


def _build_step():
    """Bench-identical train step; returns (step, args, tokens/step)."""
    import paddle_tpu as paddle
    model, opt, args, loss_call, _body, tokens = _build_parts()

    def _step(*a):
        loss = loss_call(*a)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(_step)
    return step, args, tokens


def _drain(loss):
    return float(np.asarray(loss._data))


def profile_trace(outdir, steps):
    import jax
    step, args, _ = _build_step()
    for _ in range(3):
        loss = step(*args)
    _drain(loss)
    t0 = time.perf_counter()
    with jax.profiler.trace(outdir):
        for _ in range(steps):
            loss = step(*args)
        _drain(loss)
    wall = (time.perf_counter() - t0) / steps
    print(f"profiled {steps} steps, {wall * 1e3:.1f} ms/step wall",
          file=sys.stderr)

    paths = glob.glob(os.path.join(
        outdir, "**", "*.trace.json.gz"), recursive=True)
    if not paths:
        print("no trace json produced", file=sys.stderr)
        return None
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])

    # device-track pids: process_name metadata containing TPU/device
    dev_pids = set()
    names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            nm = ev.get("args", {}).get("name", "")
            names[ev.get("pid")] = nm
            if any(k in nm.lower() for k in ("tpu", "device")):
                dev_pids.add(ev.get("pid"))
    by_cat = defaultdict(lambda: [0.0, 0.0, 0.0])  # ms, flops, bytes
    by_op = defaultdict(lambda: [0.0, 0.0, "", ""])  # ms, flops, tf_op, src
    total = 0.0
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in dev_pids:
            continue
        a = ev.get("args", {})
        dur = ev.get("dur", 0) / 1e3  # us -> ms
        cat = a.get("hlo_category", "?")
        fl = float(a.get("model_flops", 0) or 0)
        by_cat[cat][0] += dur
        by_cat[cat][1] += fl
        by_cat[cat][2] += float(a.get("raw_bytes_accessed", 0) or 0)
        # strip trailing .N so repeated instances of one HLO aggregate
        base = ev.get("name", "?").rsplit(".", 1)[0]
        rec = by_op[base]
        rec[0] += dur
        rec[1] += fl
        if not rec[2]:
            rec[2] = a.get("tf_op", "")
            rec[3] = a.get("source", "")
        total += dur
    print(f"\n== by hlo_category over {steps} steps "
          f"(tracks: {sorted(names[p] for p in dev_pids)}) ==")
    for cat, (ms, fl, by) in sorted(by_cat.items(), key=lambda kv: -kv[1][0]):
        tf = fl / (ms * 1e-3) / 1e12 if ms else 0
        gb = by / (ms * 1e-3) / 1e9 if ms else 0
        print(f"{ms / steps:9.3f} ms/step {ms / max(total, 1e-9) * 100:5.1f}%"
              f"  {tf:7.1f} TF/s {gb:8.1f} GB/s  {cat}")
    print(f"{total / steps:9.3f} ms/step  TOTAL device time")
    print(f"\n== top ops ==")
    rows = sorted(by_op.items(), key=lambda kv: -kv[1][0])[:25]
    for name, (ms, fl, tf_op, src) in rows:
        tfs = fl / (ms * 1e-3) / 1e12 if ms else 0
        print(f"{ms / steps:9.3f} ms/step {tfs:7.1f} TF/s  {name[:40]:40s}"
              f" {tf_op[:60]:60s} {src.replace('/root/repo/', '')[:50]}")
    return {"wall_ms": wall * 1e3, "device_ms": total / steps,
            "by_cat": {c: {"ms_per_step": vals[0] / steps,
                           "flops_per_step": vals[1] / steps,
                           "bytes_per_step": vals[2] / steps}
                       for c, vals in by_cat.items()},
            "top": [[n, v[0] / steps, v[2], v[3]] for n, v in rows]}


def profile_ablate(steps):
    """Ablation timing for PROF_MODEL (gpt2 default; bert/llama are the
    MFU laggards this mode exists for): build step variants with pieces
    disabled and diff the medians."""
    import paddle_tpu as paddle

    def timed(variant):
        # fresh build per variant: donation off, optimizer state fresh
        model, opt, args, loss_call, body_call, _tok = _build_parts()

        def full(*a):
            loss = loss_call(*a)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        def no_opt(*a):          # fwd+bwd only
            loss = loss_call(*a)
            loss.backward()
            return loss

        def fwd(*a):
            return loss_call(*a)

        def fwd_no_head(*a):     # backbone without heads + CE
            return body_call(*a)

        def id_attn(*a):
            # attention ablated to identity (out = q): isolates the full
            # fwd+bwd cost of the flash kernels inside the real train
            # step — every model family routes through F.sdpa
            from paddle_tpu.nn import functional as F
            real = F.scaled_dot_product_attention
            F.scaled_dot_product_attention = lambda q, *r, **kw: q
            try:
                loss = loss_call(*a)
            finally:
                F.scaled_dot_product_attention = real
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        def no_drop(*a):
            model.eval()         # dropout off; still runs backward+opt
            loss = loss_call(*a)
            model.train()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        fn = {"full": full, "fwd+bwd": no_opt, "fwd": fwd,
              "fwd_no_head": fwd_no_head, "full_id_attn": id_attn,
              "full_no_drop": no_drop}[variant]
        step = paddle.jit.to_static(fn, donate_state=False)
        for _ in range(3):
            loss = step(*args)
        _drain(loss)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = step(*args)
            _drain(loss)
            ts.append((time.perf_counter() - t0) / steps)
        return float(np.median(ts)) * 1e3

    out = {}
    for name in ("full", "fwd+bwd", "fwd", "fwd_no_head",
                 "full_id_attn", "full_no_drop"):
        out[name] = timed(name)
        print(f"{name:12s} {out[name]:8.2f} ms/step", file=sys.stderr)
    print(f"\n== ablation deltas (PROF_MODEL="
          f"{os.environ.get('PROF_MODEL', 'gpt2')}) ==")
    print(f"optimizer+writeback : {out['full'] - out['fwd+bwd']:8.2f} ms")
    print(f"backward            : {out['fwd+bwd'] - out['fwd']:8.2f} ms")
    print(f"heads + CE (fwd)    : {out['fwd'] - out['fwd_no_head']:8.2f} ms")
    print(f"body fwd            : {out['fwd_no_head']:8.2f} ms")
    print(f"attention fwd+bwd   : {out['full'] - out['full_id_attn']:8.2f} ms")
    print(f"all dropout         : {out['full'] - out['full_no_drop']:8.2f} ms")
    print(f"full step           : {out['full']:8.2f} ms")
    return out


def main():
    from paddle_tpu.device.chip import require_tpu, use_compile_cache
    require_tpu()
    use_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outdir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        repo, "chiprun_out", "tpu_profile")
    os.makedirs(outdir, exist_ok=True)
    steps = int(os.environ.get("PROF_STEPS", "10"))
    mode = os.environ.get("PROF_MODE", "both")
    rec = {}
    if mode in ("trace", "both"):
        rec["trace"] = profile_trace(outdir, steps)
    if mode in ("ablate", "both"):
        rec["ablate"] = profile_ablate(steps)
    with open(os.path.join(outdir, "summary.json"), "w") as f:
        json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
