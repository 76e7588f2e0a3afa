"""Long-context flash-attention timing (ROADMAP.md S8).

Single chip: time the Pallas flash kernel fwd+bwd at S=8k/16k (GPT-2-like
heads, bf16) and print one JSON line with ms/layer + achieved TFLOP/s.
Attention FLOPs: causal fwd 2*2*S^2*D*H*B/2; bwd ~2.5x fwd (5 dots of the
same shape vs 2).

Runs on a TPU or not at all (through the chip tool):
    python tools/longctx_bench.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    from paddle_tpu.device.chip import (device_record, require_tpu,
                                        use_compile_cache)
    dev = require_tpu()
    use_compile_cache()
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    b, h, d = (1, 12, 64)
    seqs = [8192, 16384]
    dtype = jnp.bfloat16
    rows = []
    for s in seqs:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, s, h, d), dtype)
        k = jnp.asarray(rng.randn(b, s, h, d), dtype)
        v = jnp.asarray(rng.randn(b, s, h, d), dtype)

        def loss(q, k, v):
            return jnp.sum(fa.flash_attention(
                q, k, v, causal=True).astype(jnp.float32) * 1e-3)
        g = jax.jit(jax.grad(loss, (0, 1, 2)))
        out = g(q, k, v)                       # compile + warm
        float(np.asarray(out[0]).reshape(-1)[0])
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            out = g(q, k, v)
        float(np.asarray(out[0]).reshape(-1)[0])   # host fetch = barrier
        ms = (time.perf_counter() - t0) / reps * 1000
        # causal fwd+bwd flops (fwd 2 dots + bwd 5 dots, causal half)
        flops = 0.5 * 7 * 2 * s * s * d * h * b
        rows.append({"seq": s, "fwd_bwd_ms": round(ms, 2),
                     "tflops": round(flops / (ms / 1000) / 1e12, 1)})
        print(f"longctx: S={s} {ms:.1f} ms  "
              f"{rows[-1]['tflops']} TFLOP/s", file=sys.stderr)
    record = {"metric": "flash_attention_longctx_fwd_bwd",
              "unit": "ms/layer", "batch": b, "heads": h, "head_dim": d,
              "rows": rows,
              "device": device_record(dev)}
    print(json.dumps(record))


if __name__ == "__main__":
    main()
