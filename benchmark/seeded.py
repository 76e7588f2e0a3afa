"""Arrays from a seed, on the device, in ONE jitted call, in the type they
are used in: not leaf by leaf and not on the host."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def key_of(seed):
    """A PRNG key for any whole-number seed (the driver's are larger than
    32 signed bits hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed >> 31, impl="rbg"),
                              seed & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("specs", "dtype"))
def _draw(key, specs, dtype):
    out = []
    for i, (shape, mean, std, copies) in enumerate(specs):
        a = mean + std * jax.random.normal(
            jax.random.fold_in(key, i), (copies,) + shape, jnp.float32)
        a = a.astype(dtype)
        out.append([a[j] for j in range(copies)])
    return out


def normal_arrays(seed, specs, dtype):
    """``specs`` is a sequence of ``(shape, mean, std, copies)``; returns,
    for each, ``copies`` arrays of ``shape`` drawn N(mean, std) in float32
    and cast to ``dtype``."""
    return _draw(key_of(seed), tuple(
        (tuple(s), float(m), float(d), int(c)) for s, m, d, c in specs),
        jnp.dtype(dtype))
