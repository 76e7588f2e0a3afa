#!/usr/bin/env python
"""Stacked-weight PartitionSpec coverage check (runnable standalone AND
as a tier-1 test via tests/test_mesh_serving.py).

The serving step's weights live in ONE stacked pytree
(``FusedDecoder._stacked``) that is placed with ``NamedSharding`` at
stack time per ``generation.STACKED_PARAM_SPECS``. This check makes
that table STRUCTURAL:

  1. key coverage, both directions — every key the stack can emit
     (fp, int8 AND int4-packed weight flavors) has an explicit spec
     entry (sharded or declared-replicated ``P()``), and the table
     carries no dead entries. A new param key without a spec fails
     tier-1 instead of silently replicating a possibly-huge tensor on
     every device.
  2. spec sanity — each entry's sharded axes fit the actual array rank
     and use only the 'mp' mesh axis (the serving mesh's weight axis).
  3. placement truth, probed on a real mp=2 mesh — every stacked array
     lands with EXACTLY its table spec: sharded keys hold 1/mp of the
     bytes per device, declared-replicated keys the full array; the
     int8/int4 scale mirrors of column-parallel weights (qkv_w_s /
     f1_w_s) shard WITH their weight, so a quantized stack cannot
     silently gather full weights on placement. The int4 stack is
     additionally checked STRUCTURALLY: every contracted axis packs to
     half length in int8 bytes, so the row-parallel 'mp' split lands
     on whole bytes (the pack-straddle guard made a tier-1 fact).

Runs in-process as a tier-1 test, so fleet topology state is saved and
restored around the mesh probe.

Usage: python tools/check_sharding_spec.py   (exit 0 = covered)
"""
from __future__ import annotations

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_decoder():
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import FusedMultiTransformer
    from paddle_tpu.inference.generation import FusedDecoder
    from paddle_tpu.nn.layer.common import Embedding, Linear

    V, E, H, FF, L = 64, 32, 4, 64, 2
    paddle.seed(3)
    embed = Embedding(V, E)
    fmt = FusedMultiTransformer(E, H, FF, num_layers=L,
                                normalize_before=True)
    head = Linear(E, V, bias_attr=False)
    fmt.eval()
    return FusedDecoder(fmt, embed, head, max_seq_len=64)


_MODE_VARS = ("PADDLE_TPU_DECODE_INT8_WEIGHTS",
              "PADDLE_TPU_DECODE_INT4_WEIGHTS")


def _stack_keys(dec, mode):
    """Build the decoder's stack in the given weight flavor ('fp',
    'int8' or 'int4') via the env knobs, restoring the prior env."""
    prior = {v: os.environ.get(v) for v in _MODE_VARS}
    try:
        for v in _MODE_VARS:
            os.environ.pop(v, None)
        if mode == "int8":
            os.environ["PADDLE_TPU_DECODE_INT8_WEIGHTS"] = "1"
        elif mode == "int4":
            os.environ["PADDLE_TPU_DECODE_INT4_WEIGHTS"] = "1"
        return dict(dec._stacked())
    finally:
        for v, val in prior.items():
            if val is None:
                os.environ.pop(v, None)
            else:
                os.environ[v] = val


def main(argv=None):
    import math

    from paddle_tpu.inference.generation import STACKED_PARAM_SPECS

    failures = []
    dec = _build_decoder()
    stacks = {"fp": _stack_keys(dec, "fp"),
              "int8": _stack_keys(dec, "int8"),
              "int4": _stack_keys(dec, "int4")}

    # ---- 0. int4 pack structure: two nibbles per byte along every
    # CONTRACTED axis (qkv_w/f1_w pack E, lin_w the concatenated head
    # axis, f2_w the FFN axis) — the halved axes are what make the
    # row-parallel 'mp' split fall on whole bytes, and what the byte
    # gauges' "quartered" claim rests on
    f = dec.fmt
    e_dim = int(f.qkv_weights[0]._data.shape[-1])
    ff_dim = int(f.ffn1_weights[0]._data.shape[-1])
    heads = f.num_heads * f.head_dim
    i4 = stacks["int4"]
    for k, axis, full_len in (("qkv_w", 2, e_dim), ("lin_w", 1, heads),
                              ("f1_w", 1, e_dim), ("f2_w", 1, ff_dim)):
        a = i4[k]
        if str(a.dtype) != "int8":
            failures.append(
                f"int4 stack key {k!r} has dtype {a.dtype}, expected "
                "int8 bytes holding two nibbles")
        if a.shape[axis] * 2 != full_len:
            failures.append(
                f"int4 stack key {k!r} axis {axis} is "
                f"{a.shape[axis]}, expected the packed half of "
                f"{full_len} — the contracted axis did not pack")
    for k in ("qkv_w_s", "lin_w_s", "f1_w_s", "f2_w_s"):
        if k not in i4:
            failures.append(
                f"int4 stack lost its scale mirror {k!r} — dequant "
                "cannot be applied without it")

    # ---- 1. key coverage, both directions
    emitted = set()
    for flavor, stk in stacks.items():
        emitted |= set(stk)
        for k in sorted(stk):
            if k not in STACKED_PARAM_SPECS:
                failures.append(
                    f"stacked key {k!r} ({flavor} flavor) has no "
                    "generation.STACKED_PARAM_SPECS entry — add an "
                    "explicit PartitionSpec (sharded on 'mp' or the "
                    "declared-replicated P()) so placement under a "
                    "mesh stays intentional")
    for k in sorted(set(STACKED_PARAM_SPECS) - emitted):
        failures.append(
            f"STACKED_PARAM_SPECS carries dead entry {k!r} — no weight "
            "flavor emits it; remove it (stale specs hide real "
            "coverage gaps)")

    # ---- 2. spec sanity against the real array ranks
    for flavor, stk in stacks.items():
        for k, a in sorted(stk.items()):
            spec = STACKED_PARAM_SPECS.get(k)
            if spec is None:
                continue
            for dim, names in enumerate(spec):
                if names is None:
                    continue
                if dim >= a.ndim:
                    failures.append(
                        f"spec for {k!r} shards axis {dim} but the "
                        f"{flavor} array has rank {a.ndim} "
                        f"(shape {tuple(a.shape)})")
                names = names if isinstance(names, tuple) else (names,)
                for n in names:
                    if n != "mp":
                        failures.append(
                            f"spec for {k!r} uses mesh axis {n!r} — "
                            "the serving mesh shards weights on 'mp' "
                            "only")

    # ---- 3. placement truth on a real mp=2 mesh
    from paddle_tpu.distributed.fleet import _fleet_state
    from paddle_tpu.distributed.fleet.base.topology import _HYBRID_GROUP
    from paddle_tpu.parallel import init_serving_mesh

    prior_hcg = _HYBRID_GROUP[0]
    prior_fleet = dict(_fleet_state)
    try:
        _HYBRID_GROUP[0] = None
        _fleet_state.update(strategy=None, hcg=None, initialized=False)
        mesh = init_serving_mesh(2)
        sharded_any = {}
        for flavor in ("fp", "int8", "int4"):
            stk = _stack_keys(dec, flavor)
            for k, a in sorted(stk.items()):
                spec = STACKED_PARAM_SPECS.get(k)
                if spec is None:
                    continue     # reported above
                full = tuple(a.shape)
                local = tuple(a.sharding.shard_shape(full))
                want = list(full)
                for dim, names in enumerate(spec):
                    if names is None or dim >= len(want):
                        continue
                    names = (names if isinstance(names, tuple)
                             else (names,))
                    for n in names:
                        want[dim] //= mesh.shape[n]
                if local != tuple(want):
                    failures.append(
                        f"{flavor} stack key {k!r} placed as {local} "
                        f"per device (full {full}) — its spec {spec} "
                        f"demands {tuple(want)}; the table and the "
                        "actual placement have diverged")
                sharded_any.setdefault(k, False)
                if local != full:
                    sharded_any[k] = True
        # the int8/int4 scale mirrors of column-parallel weights must
        # ride their weight's shard (the silent-gather trap)
        for k in ("qkv_w_s", "f1_w_s"):
            if k in sharded_any and not sharded_any[k]:
                failures.append(
                    f"int8 scale mirror {k!r} stayed replicated while "
                    "its column-parallel weight shards — applying it "
                    "would gather the sharded dot result every "
                    "dispatch")
        # per-device weight bytes must actually drop ~1/mp: the whole
        # point of the table
        stk = _stack_keys(dec, "fp")
        dense = sum(math.prod(a.shape) * a.dtype.itemsize
                    for a in stk.values())
        per_dev = sum(
            math.prod(a.sharding.shard_shape(tuple(a.shape)))
            * a.dtype.itemsize for a in stk.values())
        if not per_dev < dense:
            failures.append(
                f"mp=2 placement holds {per_dev} bytes per device of "
                f"a {dense}-byte dense stack — nothing sharded")
    finally:
        _HYBRID_GROUP[0] = prior_hcg
        _fleet_state.clear()
        _fleet_state.update(prior_fleet)

    if failures:
        print(f"check_sharding_spec: {len(failures)} failure(s)")
        for f_ in failures:
            print(f"  - {f_}")
        return 1
    print(
        f"check_sharding_spec: ok ({len(emitted)} stacked keys across "
        "fp+int8+int4 flavors covered by STACKED_PARAM_SPECS; specs "
        "rank-checked; int4 contracted axes pack to whole-byte halves; "
        "mp=2 placement matches the table exactly; column-parallel "
        "quant scale mirrors shard with their weights)")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, REPO_ROOT)
    # a structure check, not a measurement: standalone runs pin the CPU
    # (same lever as tests/conftest.py: the config override wins over env)
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.exit(main())
