"""Fused int4 dequant-matmul Pallas kernel for the serving step core.

The int4 stacked serving weights (PADDLE_TPU_DECODE_INT4_WEIGHTS, see
generation._stacked) pack two adjacent contracted-axis elements per int8
byte: the LOW nibble holds the even k index, the HIGH nibble the odd one,
both sign-extended 4-bit values in [-7, 7] scaled by a per-out-channel
absmax scale. A naive serving step would dequantize the whole packed
array back to fp before the dot — materializing the exact HBM copy the
quantization exists to avoid. This kernel keeps the weight packed end to
end: bytes stream from HBM, nibbles unpack in VMEM registers, and the
dot accumulates in fp32, so the weight-side HBM traffic of the step is
the packed byte stream plus the scale row (the
`fused_multi_transformer`-style weight-only fusion PAPER.md's Phi layer
names).

Nibble layout note: unpacking splits one sublane-axis byte into TWO
contracted elements, which Mosaic cannot interleave along the sublane
axis in-kernel. The wrapper therefore splits the ACTIVATION on the host
instead — `a_even = a[..., 0::2]`, `a_odd = a[..., 1::2]` — and the
kernel computes `a_even @ lo + a_odd @ hi`, which is exactly
`a @ unpacked` without any nibble shuffle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas

__all__ = ["fused_dequant_matmul", "fused_dequant_matmul_is_supported"]

# fp32 sublane minimum for the activation block / output tile
_SUBLANE = 8


def _k_block(k2):
    """Packed-axis block: the largest of 256/128/64/32 that tiles K/2
    (the whole axis when none does)."""
    for cand in (256, 128, 64, 32):
        if k2 > cand and k2 % cand == 0:
            return cand
    return k2


# the kernel holds whole-M x whole-O tiles; Mosaic's scoped VMEM limit on
# v5e is 16 MiB (the compiler reports it). The estimate in the gate below
# misses some compiler temporaries: over a 100-shape sweep against the
# v5e compiler, 10 MiB is where a yes was always a compile
_VMEM_BUDGET = 10 << 20


def fused_dequant_matmul_is_supported(m, k, o, itemsize=2) -> bool:
    """Whether the fused kernel can serve an [m, k] @ [k, o] contraction
    with the weight int4-packed along k. The pack itself only needs an
    even k; on real TPU the packed sublane axis additionally wants the
    int8 sublane minimum (K/2 % 32), a lane-aligned out axis (O % 128)
    and tiles that fit VMEM: the fp32 accumulator and the output tile
    ([M, O] each, whole), the widened weight block ([bk2, O] int32) and
    the double-buffered activation halves. Interpret mode (CPU CI) has
    none of these constraints. A yes is checked against the real
    compiler in tests/test_chip_compile.py."""
    if k % 2:
        return False
    if m <= 0 or o <= 0:
        return False
    if _pallas._interpret():
        return True
    k2 = k // 2
    if k2 % 32 or o % 128:
        return False
    mp = -(-m // _SUBLANE) * _SUBLANE
    bk2 = _k_block(k2)
    vmem = mp * o * (4 + itemsize) + 4 * bk2 * o + 4 * mp * bk2 * itemsize
    return vmem <= _VMEM_BUDGET


def _fused_dequant_mm_kernel(ae_ref, ao_ref, w_ref, s_ref, o_ref, acc_sc,
                             *, nk):
    ki = pl.program_id(0)

    @pl.when(ki == 0)
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # sign-extending nibble unpack. Widen FIRST: Mosaic on v5e has no
    # int8 vector shift (arith.shli on vector<...xi8> fails to legalize),
    # and on int32 the same arithmetic shifts give the same values
    w = w_ref[...].astype(jnp.int32)                 # [bk2, O] packed
    lo = jnp.right_shift(jnp.left_shift(w, 28), 28)  # even k
    hi = jnp.right_shift(w, 4)                       # odd k
    ae = ae_ref[...].astype(jnp.float32)
    ao = ao_ref[...].astype(jnp.float32)
    acc_sc[:] += (
        jax.lax.dot(ae, lo.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        + jax.lax.dot(ao, hi.astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    )

    @pl.when(ki == nk - 1)
    def _():
        o_ref[...] = (acc_sc[:] * s_ref[...]).astype(o_ref.dtype)


def fused_dequant_matmul(a, w_packed, scales, *, out_dtype=None):
    """`a @ dequant(w_packed, scales)` without materializing the
    dequantized weight.

    a:        [..., K] activations (any float dtype; compute is fp32)
    w_packed: [K // 2, O] int8 — low nibble = even k, high nibble =
              odd k, sign-extended int4 in [-7, 7]
    scales:   [O] or [1, O] fp per-out-channel absmax scales
    returns:  [..., O] in ``out_dtype`` (default: a.dtype)
    """
    if w_packed.dtype != jnp.int8:
        raise ValueError("fused_dequant_matmul: packed weight must be int8")
    k = a.shape[-1]
    k2, o = w_packed.shape
    if k != 2 * k2:
        raise ValueError(
            f"fused_dequant_matmul: activation K={k} does not match "
            f"packed K/2={k2}")
    s2 = jnp.reshape(scales, (1, o)).astype(jnp.float32)
    if out_dtype is None:
        out_dtype = a.dtype

    lead = a.shape[:-1]
    a2 = jnp.reshape(a, (-1, k))
    m = a2.shape[0]
    # pad the token axis up to the fp32 sublane minimum
    mp = max(_SUBLANE, -(-m // _SUBLANE) * _SUBLANE)
    if mp != m:
        a2 = jnp.pad(a2, ((0, mp - m), (0, 0)))
    # host-side even/odd split — see module docstring
    a_even = a2[:, 0::2]                             # [mp, K2]
    a_odd = a2[:, 1::2]                              # [mp, K2]

    bk2 = _k_block(k2)
    nk = k2 // bk2

    out = pl.pallas_call(
        functools.partial(_fused_dequant_mm_kernel, nk=nk),
        grid=(nk,),
        in_specs=[
            pl.BlockSpec((mp, bk2), lambda ki: (0, ki)),
            pl.BlockSpec((mp, bk2), lambda ki: (0, ki)),
            pl.BlockSpec((bk2, o), lambda ki: (ki, 0)),
            pl.BlockSpec((1, o), lambda ki: (0, 0)),
        ],
        out_specs=pl.BlockSpec((mp, o), lambda ki: (0, 0)),
        scratch_shapes=[pltpu.VMEM((mp, o), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((mp, o), out_dtype),
        name="fused_dequant_matmul_fwd",
        interpret=_pallas._interpret(),
    )(a_even, a_odd, w_packed, s2)
    if mp != m:
        out = out[:m]
    return jnp.reshape(out, lead + (o,))
