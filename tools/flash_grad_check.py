"""The flash backward's ONE-PASS kernel against the two-kernel pair and
against a float32 dense composite, on the chip, at the shapes of the two
cells that run it: ``sdar_30b.blockdiff_8k`` (1 x 16,384 positions, 32 query
/ 4 KV heads of 128, the block-diffusion mask) and
``qwen3next_80b.pretrain_8k`` (2 x 8,192, 16 / 2 heads of 256, causal), bf16;
and at GPT-2's widths past one tile (2 x 4,096, 12 heads of 64, causal) WITH
dropout 0.1 drawn inside the kernels, which interpret mode cannot run.
The benchmark's ``correct`` is an eval-mode forward and holds no gradient to
anything (PERF.md section 7 row 13), so the kernel's three gradients are
measured here.

    chiprun -- python tools/flash_grad_check.py [--seed N] [--cells sdar
        qwen3next] [--tiles 512,1024 ...]

Prints one JSON line (and writes it to
``chiprun_out/flash_grad_check.json``). For each cell: the relative L2
difference of ``dq dk dv`` of the one-pass kernel against the pair's (and
whether they are bit-equal), of each against the composite (float32 dense
scores a head, products at ``Precision.HIGHEST``, on the same bf16 inputs),
and the milliseconds a call of the forward kernel, the pair, the one-pass
kernel, and the one-pass kernel at each ``--tiles`` pair ``bq,bk``. The limit
is the pair's own distance from the composite: the one-pass kernel may stand
no further off than 1.05 x that. Under dropout no composite can draw the
kernels' mask, so there the one-pass kernel is held to the pair's gradients
(1e-6: both regenerate the forward's mask from the seed and the tile's pair).
Exits non-zero where a limit is passed or no TPU is there.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from gdn_rule_grad_check import millis, rel                     # noqa: E402
from paddle_tpu.device.chip import require_tpu                  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa         # noqa: E402

# batch, positions, query heads, KV heads, head size, mask, dropout
CELLS = {
    "sdar": (1, 16384, 32, 4, 128, fa.block_diffusion_mask(8192, 4), 0.0),
    "qwen3next": (2, 8192, 16, 2, 256, "causal", 0.0),
    "gpt2_dropout": (2, 4096, 12, 12, 64, "causal", 0.1),
}
NAMES = ("dq", "dk", "dv")
FACTOR = 1.05


def inputs(seed, b, s, h, hk, d):
    """q, k, v, do in the kernels' layout, [B, H, S, D] bf16."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((b, n, s, d), np.float32),
                             jnp.bfloat16) for n in (h, hk, hk, h))


def kernel_grads(run, mask, scale, bq, bk, drop=None):
    """``run`` is ``fa._bwd_onepass`` or ``fa._bwd_pair``, on whole tiles."""
    def seed():
        return jnp.reshape(drop[1].astype(jnp.int32), (1,))

    def grads(q, k, v, o, lse, do):
        b, h, s, d = q.shape
        hk = k.shape[1]
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        dq, dk, dv = run(q, k, v, do, lse, delta, drop, seed, mask=mask,
                         scale=scale, sq=s, sk=s, bq=bq, bk=bk, group=h // hk)
        dk, dv = (x.reshape(b, hk, h // hk, s, d).sum(axis=2).astype(k.dtype)
                  for x in (dk, dv))
        return dq, dk, dv
    return jax.jit(grads)


def composite_grads(q, k, v, do, mask, scale):
    """dq, dk, dv of dense float32 attention, a head at a time (a head's
    scores are 1 GiB at 16,384 positions)."""
    b, h, s, d = q.shape
    group = h // k.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)
    allowed = (pos[None, :] <= pos[:, None] if mask == "causal"
               else fa.dense_mask(mask))

    def dense(qh, kh, vh):
        sc = jnp.where(allowed, (qh @ kh.T) * scale, fa.NEG_INF)
        return jax.nn.softmax(sc, axis=-1) @ vh

    @jax.jit
    def head(qh, kh, vh, doh):
        with jax.default_matmul_precision("highest"):
            return jax.vjp(dense, *(x.astype(jnp.float32)
                                    for x in (qh, kh, vh)))[1](
                doh.astype(jnp.float32))
    dq = np.zeros(q.shape, np.float32)
    dk, dv = (np.zeros(k.shape, np.float32) for _ in range(2))
    for bi in range(b):
        for hi in range(h):
            g = head(q[bi, hi], k[bi, hi // group], v[bi, hi // group],
                     do[bi, hi])
            dq[bi, hi] = g[0]
            dk[bi, hi // group] += np.asarray(g[1])
            dv[bi, hi // group] += np.asarray(g[2])
    return dq, dk, dv


def check_cell(name, seed, tiles):
    b, s, h, hk, d, mask, dropout_p = CELLS[name]
    scale = d ** -0.5
    q, k, v, do = inputs(seed, b, s, h, hk, d)
    bq, bk = fa._block_sizes(s, s, d)
    drop = ("prng", jnp.int32(seed), dropout_p) if dropout_p else None
    fwd = jax.jit(lambda q, k, v: fa._fwd(q, k, v, drop, mask=mask,
                                          scale=scale, bq=bq, bk=bk))
    o, lse = fwd(q, k, v)
    onepass = kernel_grads(fa._bwd_onepass, mask, scale, bq, bk, drop)
    pair = kernel_grads(fa._bwd_pair, mask, scale, bq, bk, drop)
    got = [np.asarray(x.astype(jnp.float32))
           for x in onepass(q, k, v, o, lse, do)]
    two = [np.asarray(x.astype(jnp.float32))
           for x in pair(q, k, v, o, lse, do)]
    out = {"shape": [b, s, h, hk, d], "tiles": [bq, bk],
           "dropout_p": dropout_p,
           "vmem_limit_mib": (fa._onepass_vmem_bytes(s, bq, bk, d, 2)
                              + fa._ONEPASS_HEADROOM) / 2 ** 20,
           "onepass_vs_pair": dict(zip(NAMES, map(rel, got, two))),
           "bit_equal": [bool(np.array_equal(a, b_))
                         for a, b_ in zip(got, two)],
           "fwd_ms": millis(fwd, q, k, v),
           "pair_ms": millis(pair, q, k, v, o, lse, do),
           "onepass_ms": {f"{bq},{bk}": millis(onepass, q, k, v, o, lse, do)}}
    if drop is None:
        want = composite_grads(q, k, v, do, mask, scale)
        out["pair_vs_composite"] = dict(zip(NAMES, map(rel, two, want)))
        out["onepass_vs_composite"] = dict(zip(NAMES, map(rel, got, want)))
        out["ok"] = all(out["onepass_vs_composite"][n]
                        <= FACTOR * out["pair_vs_composite"][n]
                        for n in NAMES)
    else:
        out["ok"] = all(x <= 1e-6 for x in out["onepass_vs_pair"].values())
    for t in tiles:
        tq, tk = map(int, t.split(","))
        if s % tq or s % tk or (mask != "causal" and tq != tk):
            continue        # whole tiles; the structured mask needs bq == bk
        try:
            out["onepass_ms"][t] = millis(
                kernel_grads(fa._bwd_onepass, mask, scale, tq, tk, drop),
                q, k, v, o, lse, do)
        except Exception as e:      # a tile the compiler refuses: say so
            out["onepass_ms"][t] = f"refused: {str(e)[:200]}"
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cells", nargs="*", default=list(CELLS))
    ap.add_argument("--tiles", nargs="*", default=[],
                    help="further 'bq,bk' pairs to time the one-pass kernel")
    args = ap.parse_args()
    require_tpu()
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed}
    for name in args.cells:
        out[name] = check_cell(name, args.seed, args.tiles)
    out["ok"] = all(out[name]["ok"] for name in args.cells)
    line = json.dumps(out)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "flash_grad_check.json"),
              "w") as f:
        f.write(line + "\n")
    print(line)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
