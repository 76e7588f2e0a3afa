"""paddle_tpu.parallel — TPU-native parallel execution utilities.

This is the scaling-book recipe as a library: pick a mesh (fleet topology),
annotate shardings (layers/optimizer set PartitionSpecs), device_put the
state, jit the step — XLA inserts the all-gathers/reduce-scatters/all-reduces
the reference implements as ProcessGroupNCCL calls.

Key entry points:
  apply_shardings(mesh)  — place every persistent tensor per its spec
  shard_batch(x, mesh)   — split the batch over the data axes (dp×sharding)
  make_train_step(...)   — functional jitted train step over sharded state
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..tensor.tensor import Tensor, persistent_tensors
from .context_parallel import (ring_attention, ulysses_attention,
                               make_ring_attention_fn,
                               make_ulysses_attention_fn)

__all__ = ["apply_shardings", "shard_batch", "data_spec", "current_mesh",
           "init_serving_mesh", "with_spec", "ring_attention",
           "ulysses_attention", "make_ring_attention_fn",
           "make_ulysses_attention_fn"]


def current_mesh() -> Optional[Mesh]:
    from ..distributed.fleet.base.topology import _HYBRID_GROUP
    hcg = _HYBRID_GROUP[0]
    return hcg.mesh if hcg is not None else None


def init_serving_mesh(mp: Optional[int] = None, *,
                      num_heads: Optional[int] = None,
                      ffn_dim: Optional[int] = None,
                      head_dim: Optional[int] = None,
                      weight_quant: Optional[str] = None
                      ) -> Optional[Mesh]:
    """Stand up (or reuse) a pure tensor-parallel mesh for serving:
    dp=pp=sharding=1, mp as given (default: ``PADDLE_SERVING_MESH_MP``;
    unset/0/1 = no mesh — returns whatever mesh is already active).
    Idempotent: if the active mesh already has the requested mp degree
    it is returned as-is; a CONFLICTING active mesh raises instead of
    silently re-initializing fleet under a live engine's feet.

    Pass the model's ``num_heads`` / ``ffn_dim`` to validate the full
    tensor-parallel layout up front: the KV pool and qkv/out-proj shard
    by head and the FFN weights by column over 'mp', so an indivisible
    axis is rejected HERE with an actionable error instead of surfacing
    as a downstream XLA shape failure (or a silently replicated stack).
    With ``weight_quant='int4'`` (plus ``head_dim``) the validation
    extends to the PACKED contracted axes: int4 packs two elements per
    byte, and the row-parallel stacks (out-proj [L, nh*hd/2, E], FFN-2
    [L, ffn/2, E]) split that packed axis over 'mp' — a shard boundary
    must land on a whole byte, so the HALF lengths must divide mp too.

    This is the one-call bring-up a sharded ``ServingEngine`` needs:

        init_serving_mesh(2)          # or PADDLE_SERVING_MESH_MP=2
        eng = ServingEngine(...)      # KV pool AND the stacked weights
                                      # shard over 'mp' (opt out of the
                                      # weight half with
                                      # PADDLE_SERVING_MESH_WEIGHTS=0)
    """
    import os
    if mp is None:
        mp = int(os.environ.get("PADDLE_SERVING_MESH_MP", "0") or 0)
    mp = int(mp)
    mesh = current_mesh()
    if mp <= 1:
        return mesh
    if num_heads is not None and num_heads % mp:
        raise ValueError(
            f"init_serving_mesh(mp={mp}): num_heads={num_heads} is not "
            f"divisible by mp — the qkv/out-proj weights and the KV "
            "pool shard by head over 'mp'; pick mp from the divisors "
            f"of {num_heads}")
    if ffn_dim is not None and ffn_dim % mp:
        raise ValueError(
            f"init_serving_mesh(mp={mp}): ffn_dim={ffn_dim} is not "
            "divisible by mp — the FFN weights shard by column over "
            f"'mp'; pick mp from the divisors of {ffn_dim}")
    if weight_quant == "int4":
        if ffn_dim is not None and (ffn_dim % 2 or (ffn_dim // 2) % mp):
            raise ValueError(
                f"init_serving_mesh(mp={mp}, weight_quant='int4'): "
                f"ffn_dim={ffn_dim} must be even AND its packed half "
                f"{ffn_dim // 2} divisible by mp — the row-parallel "
                "FFN-2 stack shards its int4-PACKED contracted axis, "
                "and a shard boundary must land on a whole byte")
        if num_heads is not None and head_dim is not None:
            hh = num_heads * head_dim
            if hh % 2 or (hh // 2) % mp:
                raise ValueError(
                    f"init_serving_mesh(mp={mp}, weight_quant='int4'): "
                    f"num_heads*head_dim={hh} must be even AND its "
                    f"packed half {hh // 2} divisible by mp — the "
                    "row-parallel out-proj stack shards its "
                    "int4-PACKED contracted axis in whole bytes")
    if mesh is not None:
        have = dict(mesh.shape).get("mp", 1)
        if have == mp:
            return mesh
        raise RuntimeError(
            f"init_serving_mesh(mp={mp}): a mesh with mp={have} is "
            "already active — one process, one hybrid topology (reset "
            "fleet state before re-initializing)")
    if jax.device_count() < mp:
        raise RuntimeError(
            f"init_serving_mesh(mp={mp}) needs >= {mp} devices, found "
            f"{jax.device_count()} — on CPU hosts set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={mp} before the "
            "first jax import")
    if jax.device_count() % mp:
        raise RuntimeError(
            f"init_serving_mesh(mp={mp}): device count "
            f"{jax.device_count()} is not divisible by mp — a ragged "
            "mesh cannot be built; pick mp from the divisors of the "
            "device count (or adjust "
            "--xla_force_host_platform_device_count)")
    from ..distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": mp,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    return current_mesh()


def _valid_spec(arr, spec, mesh: Mesh) -> bool:
    """Spec axes must divide the array dims on this mesh."""
    if spec is None:
        return False
    for dim, names in enumerate(spec):
        if names is None:
            continue
        names = names if isinstance(names, tuple) else (names,)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        if dim >= arr.ndim or arr.shape[dim] % size != 0:
            return False
    return True


def apply_shardings(mesh: Optional[Mesh] = None) -> int:
    """device_put every persistent tensor according to its sharding_spec
    (replicated when unset/indivisible). Returns #sharded tensors."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return 0
    n = 0
    for t in persistent_tensors():
        arr = t._data
        if not hasattr(arr, "shape"):
            continue
        if jnp.issubdtype(arr.dtype, jnp.bool_) and arr.ndim == 0:
            continue
        spec = t.sharding_spec
        if spec is not None and _valid_spec(arr, spec, mesh):
            sh = NamedSharding(mesh, P(*spec))
            n += 1
        else:
            sh = NamedSharding(mesh, P())
        try:
            t._data = jax.device_put(arr, sh)
        except Exception:
            pass
    return n


def data_spec(ndim: int, mesh: Optional[Mesh] = None) -> P:
    """Batch dim sharded over the combined data axes (dp and the ZeRO
    sharding group both consume distinct data, exactly as Fleet does)."""
    return P(("dp", "sharding"), *([None] * (ndim - 1)))


def shard_batch(x, mesh: Optional[Mesh] = None):
    mesh = mesh or current_mesh()
    if mesh is None:
        return x
    arr = x._data if isinstance(x, Tensor) else jnp.asarray(x)
    total = mesh.shape["dp"] * mesh.shape["sharding"]
    if arr.shape[0] % total != 0:
        return x if isinstance(x, Tensor) else Tensor(arr)
    sh = NamedSharding(mesh, data_spec(arr.ndim, mesh))
    out = jax.device_put(arr, sh)
    return Tensor(out) if not isinstance(x, Tensor) else Tensor(out)


def with_spec(t: Tensor, *spec) -> Tensor:
    """Attach + apply a PartitionSpec to a tensor on the current mesh."""
    t.sharding_spec = P(*spec)
    from ..distributed.auto_parallel.api import bump_placement_generation
    bump_placement_generation()
    mesh = current_mesh()
    if mesh is not None and _valid_spec(t._data, t.sharding_spec, mesh):
        t._data = jax.device_put(t._data,
                                 NamedSharding(mesh, t.sharding_spec))
    return t
