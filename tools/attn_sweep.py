"""Flash-attention tiling microbench: fwd+bwd time at the GPT-2 headline
shape per (BQ, BK) tiling, plus the composite (non-Pallas) reference.

Times ONLY the attention op (value_and_grad of a scalar readout), so a
sweep point costs seconds, not a full bench.py compile. Runs on a TPU or
not at all (through the chip tool):

    python tools/attn_sweep.py            # default point grid
    PADDLE_TPU_FLASH_BQ=.. single point via env (bench.py parity)

Prints one JSON line per point to stdout; progress to stderr.
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

    from paddle_tpu.device.chip import require_tpu, use_compile_cache
    dev = require_tpu()
    use_compile_cache()
    import jax.numpy as jnp

    b, h, s, d = (int(os.environ.get("SWEEP_B", "8")),
                  int(os.environ.get("SWEEP_H", "12")),
                  int(os.environ.get("SWEEP_S", "1024")),
                  int(os.environ.get("SWEEP_D", "64")))
    dropout_p = float(os.environ.get("SWEEP_DROPOUT", "0.1"))
    steps = int(os.environ.get("SWEEP_STEPS", "30"))

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)

    # fwd+bwd attention FLOPs (causal ~halves): 2 fwd dots + ~7 bwd-dot
    # equivalents over the s^2 x d volume
    full_dots = 2 + 7
    flops = full_dots * 2 * b * h * s * s * d * 0.5

    points = [(256, 256), (256, 512), (512, 256), (512, 512),
              (512, 1024), (1024, 512), (1024, 1024), (128, 512)]
    if os.environ.get("SWEEP_POINTS"):
        points = [tuple(int(x) for x in p.split("x"))
                  for p in os.environ["SWEEP_POINTS"].split(",")]

    for bq, bk in points:
        os.environ["PADDLE_TPU_FLASH_BQ"] = str(bq)
        os.environ["PADDLE_TPU_FLASH_BK"] = str(bk)
        # block sizes are read from env at TRACE time (_padded_sizes), and
        # jit caches key on function identity — loss_fn/grad_fn MUST be
        # rebuilt inside this loop so each point retraces and picks up the
        # new env. Hoisting them out would silently pin every point to the
        # first tiling.
        from paddle_tpu.ops.pallas import flash_attention as fa

        def loss_fn(q, k, v, seed):
            o = fa.flash_attention(q, k, v, causal=True,
                                   dropout_p=dropout_p, dropout_seed=seed)
            return jnp.sum(o.astype(jnp.float32))

        grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1, 2)),
                          static_argnums=())
        seed = jnp.zeros((), jnp.int32)
        try:
            t_c0 = time.perf_counter()
            val, grads = grad_fn(q, k, v, seed)
            float(np.asarray(val))
            compile_s = time.perf_counter() - t_c0
            t0 = time.perf_counter()
            for _ in range(steps):
                val, grads = grad_fn(q, k, v, seed)
            float(np.asarray(val))  # host fetch waits for the device
            dt = (time.perf_counter() - t0) / steps
            print(json.dumps({
                "bq": bq, "bk": bk, "ms": round(dt * 1e3, 3),
                "tflops_eff": round(flops / dt / 1e12, 1),
                "compile_s": round(compile_s, 1),
                "dropout": dropout_p,
            }))
        except Exception as e:
            print(json.dumps({"bq": bq, "bk": bk,
                              "error": f"{type(e).__name__}: {e}"[:200]}))
        sys.stdout.flush()
        print(f"sweep: {bq}x{bk} done", file=sys.stderr)


if __name__ == "__main__":
    main()
