"""ViT-L/16 step ablation: localize the r3 11.2%-MFU laggard.

Times bench-shaped ViT-L variants (UNDONATED by default — set
PROF_DONATE=1 for donated stepping) and diffs chunk-medians:
  full          train step (fwd+bwd+AdamW), remat ON (bench config)
  no_remat      same without recompute (memory-permitting at this batch)
  no_opt        fwd+bwd only
  fwd           forward only
  full_remat_convpatch   full step with the patch CONV forced
                (PADDLE_TPU_PATCH_CONV=1) — the A/B against the new
                space-to-depth matmul default
Prints one JSON line. Runs on a TPU or not at all (through the chip
tool):  python tools/vit_profile.py
Env: PROF_STEPS (default 8), BENCH_VIT_BATCH (default 32).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from paddle_tpu.device.chip import (device_record, require_tpu,
                                        use_compile_cache)
    dev = require_tpu()
    use_compile_cache()
    import paddle_tpu as paddle
    from paddle_tpu.models.vit import vit_l_16

    steps = int(os.environ.get("PROF_STEPS", "8"))
    batch = int(os.environ.get("BENCH_VIT_BATCH", "32"))
    size = 224
    rng = np.random.RandomState(0)
    x_np = rng.randn(batch, 3, size, size).astype(np.float32)
    y_np = rng.randint(0, 10, (batch,)).astype(np.int32)

    def build(recompute=True):
        paddle.seed(0)
        m = vit_l_16(recompute=recompute)
        m.bfloat16()
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=m.parameters(),
                                     multi_precision=True)
        x = paddle.to_tensor(x_np).astype("bfloat16")
        return m, opt, x, paddle.to_tensor(y_np)

    donate = os.environ.get("PROF_DONATE") == "1"

    def timed(name, make_step, recompute=True):
        # a variant that raises (no_remat can run a 16 GB chip out of
        # memory, so it goes last) ends the run non-zero; what was
        # measured before it is already on stderr
        m, opt, x, y = build(recompute)
        step = paddle.jit.to_static(make_step(m, opt),
                                    donate_state=donate)
        for _ in range(2):
            out = step(x, y)
        float(np.asarray(out._data).sum())
        ts = []
        chunk = max(steps // 3, 1)
        for _ in range(3):          # median of chunks
            t0 = time.perf_counter()
            for _ in range(chunk):
                out = step(x, y)
            float(np.asarray(out._data).sum())
            ts.append((time.perf_counter() - t0) / chunk)
        ms = round(float(np.median(ts)) * 1e3, 2)
        print(f"vit_profile: {name} {ms} ms", file=sys.stderr)
        return ms

    def full(m, opt):
        def f(x, y):
            loss = paddle.nn.functional.cross_entropy(m(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        return f

    def no_opt(m, opt):
        def f(x, y):
            loss = paddle.nn.functional.cross_entropy(m(x), y)
            loss.backward()
            return loss
        return f

    def fwd(m, opt):
        def f(x, y):
            return paddle.nn.functional.cross_entropy(m(x), y)
        return f

    rec = {"metric": "vit_l16_step_ablation_ms", "batch": batch,
           "device": device_record(dev)}
    rec["full_remat"] = timed("full_remat", full, recompute=True)
    rec["no_opt"] = timed("no_opt", no_opt, recompute=True)
    rec["fwd"] = timed("fwd", fwd, recompute=True)
    # patch-embed A/B inside the full step: conv vs space-to-depth matmul
    os.environ["PADDLE_TPU_PATCH_CONV"] = "1"
    try:
        rec["full_remat_convpatch"] = timed("full_remat_convpatch", full,
                                            recompute=True)
    finally:
        os.environ.pop("PADDLE_TPU_PATCH_CONV", None)
    rec["full_no_remat"] = timed("full_no_remat", full, recompute=False)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
