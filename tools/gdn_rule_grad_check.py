"""The gated delta rule's backward KERNEL against the composite's own
backward, on the chip, at ``qwen3next_80b.pretrain_8k``'s shapes (2 x 8192
tokens, 16 key / 32 value heads of 128): the benchmark's ``correct`` is an
eval-mode forward and holds no gradient to anything (PERF.md section 7 row
13), so the kernel's five gradients are measured here.

    chiprun -- python tools/gdn_rule_grad_check.py [--seed N] [--ahead 4,4 4,2]

Prints one JSON line (and writes it to
``chiprun_out/gdn_rule_grad_check.json``): the relative L2 difference of each gradient (``dq dk dv dg dbeta``) of

- the kernel with float32 products against the composite with float32
  products (its products at ``Precision.HIGHEST`` as well: on the TPU a
  float32 product at the default precision is ONE bf16 pass);
- the kernel with bf16 products against that float32 composite, beside the
  CONTROL: the composite with bf16 products against it. The kernel may
  stand no further off than 1.5 x the control;

and the milliseconds a call of the forward kernel, the backward kernel (for
each ``--ahead`` pair: chunks traced in step in its forward sweep and in its
reverse walk, with the seconds the host takes to trace it) and the
composite's forward and backward.
Exits non-zero where a limit is passed or no TPU is there.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from paddle_tpu.device.chip import require_tpu                  # noqa: E402
from paddle_tpu.nn.functional import linear_attention as la     # noqa: E402
from paddle_tpu.ops.pallas import gated_delta_rule as gdr       # noqa: E402

B, T, HK, HV, D = 2, 8192, 16, 32, 128
NAMES = ("dq", "dk", "dv", "dg", "dbeta")
F32_LIMIT, CONTROL_FACTOR = 1e-4, 1.5


def inputs(seed):
    """Decays down to exp(-8) a token, as ``tests/test_gdn_rule_kernel``
    draws them; float32, as the mixer hands them to the rule."""
    rng = np.random.default_rng(seed)
    q, k = (rng.standard_normal((B, T, HK, D), np.float32) for _ in range(2))
    v, do = (rng.standard_normal((B, T, HV, D), np.float32) for _ in range(2))
    g = -np.exp(rng.uniform(-4, 2, (B, T, HV))).astype(np.float32)
    beta = rng.uniform(0, 1, (B, T, HV)).astype(np.float32)
    return tuple(map(jnp.asarray, (q, k, v, g, beta))), jnp.asarray(do)


def rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def millis(fn, *args, calls=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / calls


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ahead", nargs="*", default=[],
                    help="pairs 'sweep,walk' of chunks traced in step")
    args = ap.parse_args()
    require_tpu()
    return check(args)


def check(args):
    arrays, do = inputs(args.seed)

    def kernel_grads(mm):
        return jax.jit(lambda do, *a: jax.vjp(
            lambda *a: la._kernel_rule(*a, jnp.dtype(mm)), *a)[1](do))

    def composite_grads(mm):
        return jax.jit(lambda do, *a: jax.vjp(lambda *a: la._chunk_rule(
            *a, chunk=gdr.CHUNK, mm=jnp.dtype(mm)), *a)[1](do))

    with jax.default_matmul_precision("highest"):
        want = composite_grads(jnp.float32)(do, *arrays)
        got32 = kernel_grads(jnp.float32)(do, *arrays)
    want = [np.asarray(x) for x in want]
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "shape": [B, T, HK, HV, D]}
    out["kernel_f32_vs_composite_f32"] = dict(zip(NAMES, map(rel, got32,
                                                             want)))
    control = composite_grads(jnp.bfloat16)
    control16 = [np.asarray(x) for x in control(do, *arrays)]
    got16 = [np.asarray(x) for x in kernel_grads(jnp.bfloat16)(do, *arrays)]
    for name, a, b in (("composite_bf16_vs_composite_f32", control16, want),
                       ("kernel_bf16_vs_composite_f32", got16, want),
                       ("kernel_bf16_vs_composite_bf16", got16, control16)):
        out[name] = dict(zip(NAMES, map(rel, a, b)))

    fwd = jax.jit(lambda *a: gdr.gdn_chunk_rule_fwd(
        *a, mm=jnp.bfloat16, block_chunks=la._BLOCK_CHUNKS))
    states = fwd(*arrays)[1]
    out["fwd_kernel_ms"] = millis(fwd, *arrays)
    out["bwd_kernel_ms"] = {}
    out["bwd_trace_s"] = {}
    for pair in args.ahead or [f"{gdr._AHEAD},{gdr._AHEAD_BACK}"]:
        gdr._AHEAD, gdr._AHEAD_BACK = map(int, pair.split(","))
        # a fresh function: both are read when the kernel is traced

        def bwd(*a):
            return gdr.gdn_chunk_rule_bwd.__wrapped__(
                *a, mm=jnp.bfloat16, block_chunks=la._BLOCK_CHUNKS)
        t0 = time.perf_counter()
        jax.make_jaxpr(bwd)(*arrays, states, do)
        out["bwd_trace_s"][pair] = time.perf_counter() - t0
        out["bwd_kernel_ms"][pair] = millis(jax.jit(bwd), *arrays, states,
                                            do)
    out["composite_fwd_bwd_ms"] = millis(control, do, *arrays)

    ok = all(x <= F32_LIMIT
             for x in out["kernel_f32_vs_composite_f32"].values())
    ok &= all(out["kernel_bf16_vs_composite_f32"][n] <= CONTROL_FACTOR
              * out["composite_bf16_vs_composite_f32"][n] for n in NAMES)
    out["ok"] = bool(ok)
    line = json.dumps(out)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "gdn_rule_grad_check.json"),
              "w") as f:
        f.write(line + "\n")
    print(line)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
