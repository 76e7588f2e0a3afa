"""Compile-only probe: does XLA:TPU alias the scan-carried KV-cache
update in place, or does it copy the full cache per layer step?

The CPU backend's copy-insertion differs from TPU's, so the 2026-08-01
CPU HLO findings (two full-cache copies per layer with the old
double-operand kernel, one residual copy with the single-operand one)
need on-chip ground truth before investing in an in-kernel cache write
(pallas input_output_aliases + dynamic store). This compiles four tiny
scan bodies on the real backend — no step is executed, so it costs only
compile time — and counts cache-shaped copies in the optimized HLO:

  dus_only    : carry = DUS(carry)                  (aliasing baseline)
  dus_dense   : carry = DUS(carry); read dense      (the dense fallback)
  dus_kernel1 : carry = DUS(carry); pallas(carry)   (current design)
  dus_kernel2 : carry = DUS(carry); pallas(c, c)    (pre-r5s2 design)

Prints one JSON line. Runs on a TPU or not at all (through the chip
tool):  python tools/decode_alias_probe.py
"""
from __future__ import annotations

import functools
import json
import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax

    from paddle_tpu.device.chip import device_record, require_tpu
    dev = require_tpu()
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    L, B, H, S, D = 3, 2, 4, 256, 32
    shape = (L, 2, B, H, S, D)
    # every carry-buffer shape whose copies would defeat the design: the
    # fp cache, the int8 cache, AND the i8 mode's fp32 scales buffer
    # (the second aliased output — its aliasing is the riskier half)
    def _shape_re(prefix, dims):
        return re.compile(prefix + r"\[" + ",".join(str(d) for d in dims)
                          + r"\][^\n]*copy\(")
    carry_res = [_shape_re("f32", shape), _shape_re("s8", shape),
                 _shape_re("f32", shape[:4] + (1, shape[4]))]
    interpret = jax.default_backend() != "tpu"

    def kern1(kv_ref, o_ref):
        o_ref[...] = kv_ref[0, 0] + kv_ref[0, 1]

    def pallas1(buf):
        return pl.pallas_call(
            kern1,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, 2, 1, 1, S, D),
                                   lambda b: (0, 0, b, 0, 0, 0))],
            out_specs=pl.BlockSpec((1, 1, S, D), lambda b: (b, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, 1, S, D), jnp.float32),
            interpret=interpret)(buf)

    def kern2(k_ref, v_ref, o_ref):
        o_ref[...] = k_ref[0, 0] + v_ref[0, 0]

    def pallas2(buf):
        return pl.pallas_call(
            kern2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, 1, 1, 1, S, D),
                                   lambda b: (0, 0, b, 0, 0, 0)),
                      pl.BlockSpec((1, 1, 1, 1, S, D),
                                   lambda b: (0, 1, b, 0, 0, 0))],
            out_specs=pl.BlockSpec((1, 1, S, D), lambda b: (b, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, 1, S, D), jnp.float32),
            interpret=interpret)(buf, buf)

    def dus(buf, i):
        return jax.lax.dynamic_update_slice(
            buf, jnp.ones((1, 1, B, 1, 1, D)), (i, 0, 0, 0, 5, 0))

    def body_only(buf, i):
        buf = dus(buf, i)
        return buf, jnp.float32(0)

    def body_dense(buf, i):
        buf = dus(buf, i)
        o = jax.lax.dynamic_index_in_dim(buf, i, 0, keepdims=False)
        return buf, o.sum()

    def body_k1(buf, i):
        buf = dus(buf, i)
        return buf, pallas1(buf).sum()

    def body_k2(buf, i):
        buf = dus(buf, i)
        return buf, pallas2(buf).sum()

    # the production fused write+attend kernel (input_output_aliases, no
    # XLA-side DUS at all) — compiling it here also front-runs its first
    # Mosaic compile (dynamic-offset store, aliased output) before the
    # bench phase spends minutes on it
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_stacked_write)
    q = jnp.zeros((B, H, 1, D), jnp.float32)
    kvn = jnp.zeros((2, B, H, 1, D), jnp.float32)
    lens = jnp.full((B,), 7, jnp.int32)

    def body_kw(buf, i):
        buf, o = decode_attention_stacked_write(q, kvn, buf, i, lens)
        return buf, o.sum()

    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_stacked_i8_write)
    buf_i8 = jnp.zeros(shape, jnp.int8)
    buf_sc = jnp.zeros(shape[:4] + (1, shape[4]), jnp.float32)

    def body_kw_i8(carry, i):
        ci, sc = carry
        ci, sc, o = decode_attention_stacked_i8_write(q, kvn, ci, sc, i,
                                                      lens)
        return (ci, sc), o.sum()

    out = {"device": device_record(dev),
           "cache_bytes": int(np.prod(shape)) * 4}
    for name, body, init in (
            ("dus_only", body_only, None), ("dus_dense", body_dense, None),
            ("dus_kernel1", body_k1, None), ("dus_kernel2", body_k2, None),
            ("kernel_write", body_kw, None),
            ("kernel_write_i8", body_kw_i8, (buf_i8, buf_sc))):
        try:
            fn = jax.jit(functools.partial(jax.lax.scan, body,
                                           xs=jnp.arange(L)))
            txt = fn.lower(init if init is not None
                           else jnp.zeros(shape, jnp.float32)
                           ).compile().as_text()
            out[name] = {"full_cache_copies":
                         sum(len(r.findall(txt)) for r in carry_res)}
        except Exception as e:  # a compile failure is itself a finding
            out[name] = {"error": f"{type(e).__name__}: {e}"[:200]}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
