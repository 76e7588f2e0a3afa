"""AOT-compile the main-path Pallas kernels for a DESCRIBED TPU v5e.

Interpret mode (what every other kernel test runs on the CPU) cannot see
what the chip's compiler refuses: an int8 vector shift, a mis-typed
broadcast, a block that overflows VMEM. The TPU compiler is installed in
the sandbox and compiles for a topology that is described, not attached
(on-chip-measurement guide, section 2.3), so one case per kernel entry
point at the widths ``chip_smoke.py`` drives (GPT-2-124M serving: B 8,
H 12, D 64, L 12, Smax 1024, bf16) guards every later PR at no chip time.

A compile that passes is NOT a chip run: nothing executes here, so these
cases say nothing about results or times.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)


_TOPO = None    # the described v5e:2x2, once a test of this file has started


@pytest.fixture(scope="module")
def topo():
    """Describes the topology, which loads the TPU's library: inside a
    fixture, so that importing this file (every xdist worker does) never
    does (on-chip-measurement guide, section 2)."""
    global _TOPO
    try:
        from jax.experimental import topologies
        _TOPO = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / unknown topology: skip, say why
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    return _TOPO

# chip_smoke.py's serving widths
B, H, D, L, SMAX = 8, 12, 64, 12, 1024
BF = jnp.bfloat16
F32 = jnp.float32
I8 = jnp.int8
I32 = jnp.int32


@pytest.fixture(autouse=True)
def _compile_for_the_chip(monkeypatch, topo):
    """Kernels lower through Mosaic (not the interpreter), and the
    persistent compile cache stays off: an AOT TPU executable written
    from the CPU cannot be read back and would warn on the next run."""
    import paddle_tpu.ops.pallas as pallas
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(pallas, "_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _one(shape, dtype):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(_TOPO.devices[0]))


def _mesh4(axis):
    return Mesh(np.array(_TOPO.devices).reshape(4), (axis,))


def _on(mesh, shape, dtype, *spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P(*spec)))


# "%flash_attention_fwd.1 = ... custom-call(...), custom_call_target=
# "tpu_custom_call"": the instruction's name is the device trace's event name
_KERNEL_INSTR = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = .*custom_call_target=\"tpu_custom_call\"",
    re.M)


def _compile(fn, *args):
    """Lower + compile for the described chip; the kernel must be IN the
    program (a stale interpret-mode trace would compile trivially), named
    after the ``name=`` of its ``pallas_call``, which is what a device trace,
    and ``breakdown.device_ops``, then calls the kernel. JAX wraps the name
    in the transforms it went through: the backward of ``flash_attention``
    compiles to ``%transpose_jvp_flash_attention_bwd_fused__.1``."""
    from paddle_tpu.testing import pallas_call_sites
    compiled = jax.jit(fn).lower(*args).compile()
    kernels = _KERNEL_INSTR.findall(compiled.as_text())
    assert kernels, "no tpu_custom_call in the compiled program"
    known = {name for _, _, name in pallas_call_sites() if name}
    for k in kernels:
        assert any(name in k for name in known), (k, sorted(known))
    return compiled


def _sum_grad(fn, argnums):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(F32)), argnums=argnums)


# ------------------------------------------------------------ case builders
def _pool(bt):
    nb = B * (SMAX // bt)
    return ((L, 2, nb, H, bt, D), (L, 2, nb, H, 1, bt), (B, SMAX // bt))


def _paged(sq, bt):
    from paddle_tpu.ops.pallas.decode_attention import decode_attention_paged
    pool, _, tbl = _pool(bt)
    return decode_attention_paged, (
        _one((B, H, sq, D), BF), _one(pool, BF), _one(tbl, I32),
        _one((), I32), _one((B,), I32))


def _paged_i8(sq, bt):
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_paged_i8)
    pool, sc, tbl = _pool(bt)
    return decode_attention_paged_i8, (
        _one((B, H, sq, D), BF), _one(pool, I8), _one(sc, F32),
        _one(tbl, I32), _one((), I32), _one((B,), I32))


def _flat_args(t, bt, quant):
    from paddle_tpu.ops.pallas.decode_attention import FLAT_CHUNK
    pool, sc, tbl = _pool(bt)
    nc = t // FLAT_CHUNK
    pools = ((_one(pool, I8), _one(sc, F32)) if quant
             else (_one(pool, BF),))
    return (_one((t, H, D), BF),) + pools + (
        _one(tbl, I32), _one((nc,), I32), _one((nc,), I32),
        _one((nc,), I32), _one((), I32))


def _paged_flat(t, bt):
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_paged_flat)
    return decode_attention_paged_flat, _flat_args(t, bt, False)


def _paged_flat_i8(t, bt):
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_paged_flat_i8)
    return decode_attention_paged_flat_i8, _flat_args(t, bt, True)


_RING = (L, 2, B, H, SMAX, D)
_RING_SC = (L, 2, B, H, 1, SMAX)


def _stacked(sq):
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_stacked)
    return decode_attention_stacked, (
        _one((B, H, sq, D), BF), _one(_RING, BF), _one((), I32),
        _one((B,), I32))


def _stacked_i8(sq):
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_stacked_i8)
    return decode_attention_stacked_i8, (
        _one((B, H, sq, D), BF), _one(_RING, I8), _one(_RING_SC, F32),
        _one((), I32), _one((B,), I32))


def _stacked_write():
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_stacked_write)
    return decode_attention_stacked_write, (
        _one((B, H, 1, D), BF), _one((2, B, H, 1, D), BF),
        _one(_RING, BF), _one((), I32), _one((B,), I32))


def _stacked_i8_write():
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_stacked_i8_write)
    return decode_attention_stacked_i8_write, (
        _one((B, H, 1, D), BF), _one((2, B, H, 1, D), BF),
        _one(_RING, I8), _one(_RING_SC, F32), _one((), I32),
        _one((B,), I32))


def _dense_decode():
    from paddle_tpu.ops.pallas.decode_attention import decode_attention
    return decode_attention, (
        _one((B, 1, H, D), BF), _one((B, SMAX, H, D), BF),
        _one((B, SMAX, H, D), BF), _one((B,), I32))


_QKV = (B, 1024, H, D)     # the gpt2_124m train step: 8 x 1024 x 12 x 64


def _flash(grad, dropout_p=0.0, qkv=_QKV, kv_heads=None, block=None,
           backward=(), dtype=BF, causal=True):
    """``block``: under the block-diffusion mask over ``qkv``'s positions,
    in blocks of that many tokens, instead of the causal one. The kernels
    keep their names: which mask an event ran under is the program's to
    know (``paddle_flash_mask_kernel_traces_total``), not the trace's.
    ``backward``: the backward's kernels the compiled program must name
    (``fused``: one tile a plane; ``onepass``: several; ``dkv`` and ``dq``:
    a plane past the one-pass kernel's VMEM budget)."""
    from paddle_tpu.ops.pallas.flash_attention import (block_diffusion_mask,
                                                       flash_attention)
    fn = functools.partial(flash_attention,
                           causal=causal and block is None,
                           dropout_p=dropout_p,
                           mask=block and block_diffusion_mask(
                               qkv[1] // 2, block))
    if grad:
        fn = _sum_grad(fn, (0, 1, 2))
    kv = qkv if kv_heads is None else qkv[:2] + (kv_heads,) + qkv[3:]
    return (fn, (_one(qkv, dtype), _one(kv, dtype), _one(kv, dtype)),
            "flash_attention_fwd") + tuple(
                "flash_attention_bwd_" + n for n in backward)


def _ring_chunk(grad):
    from paddle_tpu.ops.pallas.ring_chunk_attention import (
        ring_chunk_attention)

    def fwd(q, k, v, off):
        return ring_chunk_attention(q, k, v, off)[0]
    fn = _sum_grad(fwd, (0, 1, 2)) if grad else fwd
    # one ring step at seq 4096 over 4 chips: local chunk 1024
    return fn, (_one((1, 16, 1024, 64), BF),) * 3 + (_one((), I32),)


def _gdn_rule(grad):
    """The gated delta rule at ``qwen3next_80b.pretrain_8k``'s shapes, fed
    as the mixer holds its arrays, ``[B, T, H * 128]`` float32 with bf16
    products: the forward kernel, and with ``grad`` the backward kernel
    behind it, whose tiles and scratch ask for more than the default 16 MiB
    of VMEM (``gated_delta_rule._bwd_vmem_bytes``)."""
    from paddle_tpu.nn.functional import linear_attention as la
    b, t, hk, hv, d = 2, 8192, 16, 32, 128

    def fwd(q, k, v, g, beta):
        o = la._kernel_rule(q.reshape(b, t, hk, d), k.reshape(b, t, hk, d),
                            v.reshape(b, t, hv, d), g, beta, jnp.dtype(BF))
        return o.reshape(b, t, hv * d)
    fn = _sum_grad(fwd, (0, 1, 2, 3, 4)) if grad else fwd
    return fn, (_one((b, t, hk * d), F32), _one((b, t, hk * d), F32),
                _one((b, t, hv * d), F32), _one((b, t, hv), F32),
                _one((b, t, hv), F32)), "gdn_chunk_rule_fwd", *(
                    ["gdn_chunk_rule_bwd"] if grad else [])


def _dequant(m, k, o):
    from paddle_tpu.ops.pallas.fused_dequant_matmul import (
        fused_dequant_matmul)
    return fused_dequant_matmul, (
        _one((m, k), BF), _one((k // 2, o), I8), _one((1, o), F32))


# --- shard_map forms: the mp=4 serving mesh splits the 12 heads 3 a chip
def _mp_specs(mesh, quant, bt):
    pool, sc, tbl = _pool(bt)
    psp = (None, None, None, "mp", None, None)
    pools = ((_on(mesh, pool, I8, *psp), _on(mesh, sc, F32, *psp))
             if quant else (_on(mesh, pool, BF, *psp),))
    return pools, _on(mesh, tbl, I32)


def _paged_mp(sq, bt, quant):
    from jax import shard_map
    from paddle_tpu.ops.pallas import decode_attention as da
    mesh = _mesh4("mp")
    hsp = P(None, "mp", None, None)
    psp = P(None, None, None, "mp", None, None)
    pools, tbl = _mp_specs(mesh, quant, bt)
    kern = (da.decode_attention_paged_i8 if quant
            else da.decode_attention_paged)
    fn = shard_map(kern, mesh=mesh,
                   in_specs=(hsp,) + (psp,) * len(pools) + (P(), P(), P()),
                   out_specs=hsp, check_vma=False)
    return fn, (_on(mesh, (B, H, sq, D), BF, None, "mp"),) + pools + (
        tbl, _on(mesh, (), I32), _on(mesh, (B,), I32))


def _paged_flat_mp(t, bt, quant):
    from jax import shard_map
    from paddle_tpu.ops.pallas import decode_attention as da
    mesh = _mesh4("mp")
    qsp = P(None, "mp", None)
    psp = P(None, None, None, "mp", None, None)
    pools, tbl = _mp_specs(mesh, quant, bt)
    kern = (da.decode_attention_paged_flat_i8 if quant
            else da.decode_attention_paged_flat)
    nc = t // da.FLAT_CHUNK
    fn = shard_map(kern, mesh=mesh,
                   in_specs=(qsp,) + (psp,) * len(pools) + (P(),) * 5,
                   out_specs=qsp, check_vma=False)
    meta = tuple(_on(mesh, (nc,), I32) for _ in range(3))
    return fn, (_on(mesh, (t, H, D), BF, None, "mp"),) + pools + (
        tbl,) + meta + (_on(mesh, (), I32),)


def _stacked_mp(quant):
    from jax import shard_map
    from paddle_tpu.ops.pallas import decode_attention as da
    mesh = _mesh4("mp")
    hsp = P(None, "mp", None, None)
    csp = (None, None, None, "mp", None, None)
    caches = ((_on(mesh, _RING, I8, *csp), _on(mesh, _RING_SC, F32, *csp))
              if quant else (_on(mesh, _RING, BF, *csp),))
    kern = (da.decode_attention_stacked_i8 if quant
            else da.decode_attention_stacked)
    fn = shard_map(kern, mesh=mesh,
                   in_specs=(hsp,) + (P(*csp),) * len(caches) + (P(), P()),
                   out_specs=hsp, check_vma=False)
    return fn, (_on(mesh, (B, H, 1, D), BF, None, "mp"),) + caches + (
        _on(mesh, (), I32), _on(mesh, (B,), I32))


def _flash_hybrid(monkeypatch):
    # the training attention under the Fleet hybrid mesh (mp 2 x sharding
    # 2): nn/functional/attention.py runs the kernel per shard, because
    # jax refuses to auto-partition a Mosaic kernel
    import paddle_tpu.parallel as parallel
    from paddle_tpu.nn.functional.attention import _per_shard
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    mesh = Mesh(np.array(_TOPO.devices).reshape(1, 1, 2, 1, 2),
                ("pp", "dp", "sharding", "sep", "mp"))
    monkeypatch.setattr(parallel, "current_mesh", lambda: mesh)
    qkv = (8, 1024, 16, 64)
    wrap = _per_shard(qkv, qkv)

    def kern(q, k, v, seed):
        return flash_attention(q, k, v, causal=True, dropout_seed=seed)
    fn = _sum_grad(lambda q, k, v: wrap(kern)(q, k, v, jnp.int32(0)),
                   (0, 1, 2))
    return fn, (_on(mesh, qkv, BF, ("dp", "sharding"), None, "mp"),) * 3


def _ring_sep(monkeypatch):
    # the combined ring + kernel path: sequence 4096 over the 4-chip
    # 'sep' axis; the existing opt-in makes the dispatcher take the
    # kernel although jax.default_backend() is the CPU here
    from paddle_tpu.parallel.context_parallel import make_ring_attention_fn
    monkeypatch.setenv("PADDLE_TPU_RING_KERNEL_CPU", "1")
    mesh = _mesh4("sep")
    fn = make_ring_attention_fn(mesh, "sep", causal=True)
    return fn, (_on(mesh, (1, 4096, 16, 64), BF, None, "sep"),) * 3


_CASES = {
    # the serving step's attention reads (default engine: Bt 64; the
    # budget core attends a [B, 16] block, its decode tail one token)
    "paged_sq1": lambda mp: _paged(1, 64),
    "paged_sq16": lambda mp: _paged(16, 64),
    "paged_sq64": lambda mp: _paged(64, 64),
    "paged_i8_sq1": lambda mp: _paged_i8(1, 32),
    "paged_i8_sq16": lambda mp: _paged_i8(16, 32),
    "paged_flat_t128": lambda mp: _paged_flat(128, 64),
    "paged_flat_i8_t128": lambda mp: _paged_flat_i8(128, 32),
    # the dense-ring flavours (PADDLE_SERVING_PAGED=0 / generate())
    "stacked_sq1": lambda mp: _stacked(1),
    "stacked_sq16": lambda mp: _stacked(16),
    "stacked_i8_sq1": lambda mp: _stacked_i8(1),
    "stacked_write": lambda mp: _stacked_write(),
    "stacked_i8_write": lambda mp: _stacked_i8_write(),
    "decode_attention_dense": lambda mp: _dense_decode(),
    # the gpt2_124m train step
    "flash_fwd": lambda mp: _flash(False),
    "flash_fwd_bwd": lambda mp: _flash(True, backward=("fused",)),
    "flash_fwd_bwd_dropout": lambda mp: _flash(True, 0.1,
                                               backward=("fused",)),
    # past one block a side the backward is ONE kernel still: dQ is summed
    # in a plane-sized VMEM accumulator beside dK and dV, with the VMEM it
    # asks for (``_onepass_vmem_bytes``); also with the in-kernel dropout
    "flash_fwd_bwd_seq4096": lambda mp: _flash(
        True, qkv=(2, 4096, H, D), backward=("onepass",)),
    "flash_fwd_bwd_dropout_seq4096": lambda mp: _flash(
        True, 0.1, qkv=(2, 4096, H, D), backward=("onepass",)),
    # the budget's edge, ``_ONEPASS_DQ_BYTES``: float32 at 32,768 positions
    # of head 128 asks for the most the one-pass kernel ever does (85 MiB;
    # the compiler allocates 63.1); a longer plane takes the dK/dV + dQ pair
    "flash_fwd_bwd_f32_seq32768": lambda mp: _flash(
        True, qkv=(1, 32768, 2, 128), dtype=F32, backward=("onepass",)),
    "flash_fwd_bwd_seq33792": lambda mp: _flash(
        True, qkv=(1, 33792, 2, 128), backward=("dkv", "dq")),
    # Qwen3-Next's gated attention at the benchmark's 2 x 8192: head 256
    # (512-wide tiles: 1024 outgrow the default VMEM in the forward), 16
    # query heads on 2 KV heads, per-query-head dK / dV summed over 8
    "flash_fwd_gqa_d256_seq8192": lambda mp: _flash(
        False, qkv=(2, 8192, 16, 256), kv_heads=2),
    "flash_fwd_bwd_gqa_d256_seq8192": lambda mp: _flash(
        True, qkv=(2, 8192, 16, 256), kv_heads=2, backward=("onepass",)),
    # SDAR's attention at the benchmark's 1 x 8192 data tokens: 16,384
    # positions of [noisy ; clean] under the block-diffusion mask, blocks
    # of 4, 32 query heads of 128 on 4 KV heads; and blocks that are no
    # power of two (a division where the others shift) on a ragged length
    "flash_fwd_blockdiff_d128_seq16384": lambda mp: _flash(
        False, qkv=(1, 16384, 32, 128), kv_heads=4, block=4),
    "flash_fwd_bwd_blockdiff_d128_seq16384": lambda mp: _flash(
        True, qkv=(1, 16384, 32, 128), kv_heads=4, block=4,
        backward=("onepass",)),
    "flash_fwd_bwd_blockdiff_block12_seq3000": lambda mp: _flash(
        True, qkv=(2, 3000, 8, 64), kv_heads=1, block=12,
        backward=("onepass",)),
    # Qwen3-Next's gated delta rule at the benchmark's 2 x 8192
    "gdn_rule_fwd_seq8192": lambda mp: _gdn_rule(False),
    "gdn_rule_fwd_bwd_seq8192": lambda mp: _gdn_rule(True),
    "ring_chunk_fwd": lambda mp: _ring_chunk(False),
    "ring_chunk_fwd_bwd": lambda mp: _ring_chunk(True),
    # weight_quant="int4": the four matmuls of a layer, decode rows
    # (M 8) and a budget block (M 8 x 16)
    "dequant_qkv_m8": lambda mp: _dequant(8, 768, 2304),
    "dequant_proj_m8": lambda mp: _dequant(8, 768, 768),
    "dequant_ffn1_m8": lambda mp: _dequant(8, 768, 3072),
    "dequant_ffn2_m8": lambda mp: _dequant(8, 3072, 768),
    "dequant_ffn1_m128": lambda mp: _dequant(128, 768, 3072),
    "dequant_ffn2_m128": lambda mp: _dequant(128, 3072, 768),
    # shard_map forms over the described 4-chip mesh
    "mp4_paged_sq1": lambda mp: _paged_mp(1, 64, False),
    "mp4_paged_sq16": lambda mp: _paged_mp(16, 64, False),
    "mp4_paged_i8_sq1": lambda mp: _paged_mp(1, 32, True),
    "mp4_paged_flat_t128": lambda mp: _paged_flat_mp(128, 64, False),
    "mp4_paged_flat_i8_t128": lambda mp: _paged_flat_mp(128, 32, True),
    "mp4_stacked_sq1": lambda mp: _stacked_mp(False),
    "mp4_stacked_i8_sq1": lambda mp: _stacked_mp(True),
    "hybrid4_flash_fwd_bwd": _flash_hybrid,
    "sep4_ring_attention": _ring_sep,
}


@pytest.mark.parametrize("name", list(_CASES))
def test_kernel_compiles_for_v5e(name, monkeypatch):
    fn, args, *named = _CASES[name](monkeypatch)
    text = _compile(fn, *args).as_text()
    for kernel in named:        # the device trace's event name
        assert any(kernel in k for k in _KERNEL_INSTR.findall(text)), kernel


# "%copy.12 = f32[2,8192,4096]{2,1,0:T(8,128)} copy(...)": name, shape with
# its layout, opcode
_INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", re.M)
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_MOVES = {"reshape", "copy", "slice", "broadcast", "pad", "transpose",
          "concatenate"}


def test_gated_deltanet_mixer_keeps_one_layout(topo, monkeypatch):
    """One ``Qwen3NextGatedDeltaNet`` at the published widths on the
    benchmark's 2 x 8192 tokens, bf16, in train mode: forward, the replay
    under ``recompute`` and the backward pass in one program. From its
    projections to its rule the mixer's arrays keep the sequence in the
    sublanes and a head's 128 features in the lanes, so XLA has nothing to
    copy into another tiling: held here by what the ENTRY computation's
    plain data-movement instructions (fusions not counted) write, by the
    absence of a (2, 128) tile among them (a size-2 axis in the sublanes),
    by ONE call of each of the rule's kernels (the region keeps what the
    forward wrote; the replay does not run it again) and by the program's
    temporaries. These are BYTES of a compile, not times: PERF.md section 6
    (PR 29, PR 35) says which of them turned into time on the chip. Before
    PR 29: 8.19 GiB, nine such tiles, 5.02 GiB."""
    import paddle_tpu as paddle
    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.distributed.fleet.utils.recompute_mod import recompute
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextGatedDeltaNet)
    from paddle_tpu.tensor.tensor import Tensor
    monkeypatch.setattr(pallas, "_enabled", lambda: True)   # as on the chip
    paddle.seed(0)
    mixer = Qwen3NextGatedDeltaNet(Qwen3NextConfig())
    mixer.bfloat16()
    mixer.train()
    params = list(mixer.parameters())
    held = [p._data for p in params]

    def step(arrays, x):
        for p, a in zip(params, arrays):
            p._data, p.grad = a, None
        x = Tensor(x, stop_gradient=False)
        y = recompute(mixer, x)
        (y.astype("float32") ** 2).sum().backward()
        return [p.grad._data for p in params], x.grad._data

    try:
        compiled = jax.jit(step).lower(
            [_one(a.shape, a.dtype) for a in held],
            _one((2, 8192, 2048), BF)).compile()
    finally:
        for p, a in zip(params, held):
            p._data, p.grad = a, None
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    itemsize = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}
    moved, small_tiles = 0, []
    for shape, opcode in _INSTR.findall(entry):
        if opcode in _MOVES:
            kind, dims = _ARRAY.match(shape).groups()
            size = itemsize[kind] * int(np.prod(
                [int(d) for d in dims.split(",") if d]))
            moved += size
            if "T(2,128)" in shape and size >= 1 << 20:  # not a weight's row
                small_tiles.append(shape)
    gib = float(1 << 30)
    assert not small_tiles, small_tiles
    # PR 29 left 2.66 GiB: four tile-for-tile copies a pass (v in, o out and
    # their cotangents) and the scans' zero-filled results. The rule's
    # forward kernel reads and writes [B, T, H * 128] in place, so both
    # forward passes move nothing; what is left, 1.42 GiB, is the composite
    # BACKWARD's: v and o's cotangent into its blocks (256 + 128 MiB), the
    # gradients of q, k and v out of them (128 + 128 + 256) and the reverse
    # scan's zero-filled results (the same again). Since PR 33 the region
    # keeps the kernel's ``o`` and ``states`` for its replay: ONE call of
    # the kernel in forward + replay + backward, 1.41 GiB moved, and
    # temporaries 2.99 GiB (2.84 when the replay ran the kernel again and
    # nothing of it outlived the forward). Since PR 35 the backward is a
    # kernel over the same layout: 0.045 GiB moved (the gates, a chunk a
    # row), temporaries 2.43 GiB.
    kernels = _KERNEL_INSTR.findall(text)
    for name in ("gdn_chunk_rule_fwd", "gdn_chunk_rule_bwd"):
        assert sum(name in k for k in kernels) == 1, (name, kernels)
    assert moved / gib <= 0.1, moved / gib
    assert compiled.memory_analysis().temp_size_in_bytes / gib <= 2.6


def test_sdar_layer_compiles_with_the_masked_kernels(topo, monkeypatch):
    """One ``SDARDecoderLayer`` at the published widths with 16 of 128
    experts held, on the benchmark's 1 x 8192 data tokens (16,384 positions),
    bf16, in train mode: forward, the replay under ``recompute`` and the
    backward pass in one program, as the cell's step holds six of. The
    two flash kernels are in it under the structured mask (no dense
    [16384, 16384] mask is: the composite's scores alone would be 34 GB),
    each ONCE: the region keeps the forward kernel's ``o`` and ``lse`` and
    the replay does not run it again (twice before PR 33), and the backward
    is the one-pass kernel (the dK/dV + dQ pair before PR 38). Its
    temporaries (1.7714 GiB; 1.7715 with the pair, 1.90 with the replayed
    forward) leave the step room beside its state. BYTES of a compile, not
    times."""
    import paddle_tpu as paddle
    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.inference import telemetry
    from paddle_tpu.models.sdar import SDARConfig, SDARDecoderLayer
    from paddle_tpu.tensor.tensor import Tensor
    monkeypatch.setattr(pallas, "_enabled", lambda: True)   # as on the chip
    paddle.seed(0)
    layer = SDARDecoderLayer(SDARConfig(experts_held=list(range(16)),
                                        recompute=True))
    layer.bfloat16()
    layer.train()
    params = list(layer.parameters())
    held = [p._data for p in params]
    counts = layer.mlp.counts._data
    kernel = telemetry.runtime_counter("paddle_flash_mask_kernel_traces_total")

    def step(arrays, x, pos):
        for p, a in zip(params, arrays):
            p._data, p.grad = a, None
        x = Tensor(x, stop_gradient=False)
        y = layer(x, Tensor(pos))
        (y.astype("float32") ** 2).sum().backward()
        return [p.grad._data for p in params], x.grad._data

    try:
        compiled = jax.jit(step).lower(
            [_one(a.shape, a.dtype) for a in held],
            _one((1, 16384, 2048), BF), _one((16384,), I32)).compile()
    finally:
        for p, a in zip(params, held):
            p._data, p.grad = a, None
        layer.mlp.counts._data = counts
    kernels = _KERNEL_INSTR.findall(compiled.as_text())
    for name, calls in (("flash_attention_fwd", 1),
                        ("flash_attention_bwd_onepass", 1),
                        ("flash_attention_bwd_dkv", 0),
                        ("flash_attention_bwd_dq", 0)):
        assert sum(name in k for k in kernels) == calls, (name, kernels)
    # the first forward and its replay each traced the dispatch once
    assert telemetry.runtime_counter(
        "paddle_flash_mask_kernel_traces_total") == kernel + 2
    assert compiled.memory_analysis().temp_size_in_bytes / (1 << 30) <= 1.78


def _mosaic_texts(fn, args, monkeypatch):
    """The Mosaic text (no locations; the serialised bytecode carries line
    numbers and always differs) of every kernel ``fn`` lowers, in order."""
    from jax._src import tpu_custom_call
    real = tpu_custom_call._lower_mosaic_module_to_asm
    texts = []

    def spy(module, **kw):
        texts.append(module.operation.get_asm(enable_debug_info=False))
        return real(module, **kw)
    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", spy)
    jax.jit(fn).lower(*args)
    return texts


# sha256 of the kernels' Mosaic text on PR 36's tree, before the backward
# over several tiles became one kernel: the forward kernels under each mask
# and the one-tile backward (GPT-2's) are that tree's, byte for byte
_MOSAIC = {
    "causal_one_tile": (lambda: _flash(True), ["14d95a379edd9d13", "e74f95c6994c2d8f"]),
    "causal_one_tile_dropout": (lambda: _flash(True, 0.1),
                                ["aa62095e94136647", "fcd46e1c1f68ca0a"]),
    "unmasked_one_tile": (lambda: _flash(True, causal=False),
                          ["a16edb10c9c2698a", "1dc9e654deca9f0c"]),
    "causal_seq4096": (lambda: _flash(False, qkv=(2, 4096, H, D)),
                       ["6fbf8654bb67b14e"]),
    "unmasked_seq4096": (lambda: _flash(False, qkv=(2, 4096, H, D),
                                        causal=False), ["e0af01ae03d20bca"]),
    "causal_gqa_d256_seq8192": (lambda: _flash(
        False, qkv=(2, 8192, 16, 256), kv_heads=2), ["1dcbd2ccb529554c"]),
    "blockdiff_d128_seq16384": (lambda: _flash(
        False, qkv=(1, 16384, 32, 128), kv_heads=4, block=4), ["ea67dfbda186f4db"]),
}


@pytest.mark.parametrize("case", list(_MOSAIC))
def test_untouched_kernels_keep_their_mosaic_text(case, monkeypatch):
    import hashlib
    build, want = _MOSAIC[case]
    fn, args, *_ = build()
    got = [hashlib.sha256(t.encode()).hexdigest()[:16]
           for t in _mosaic_texts(fn, args, monkeypatch)]
    assert got == want


@pytest.mark.parametrize("m,k,o,says", [
    (512, 768, 2304, True), (1024, 3072, 768, True),
    (1024, 768, 3072, False), (8, 768, 50304, False),
])
def test_fused_dequant_gate_tells_the_truth(m, k, o, says):
    """Off interpret mode a yes from the gate is a promise that Mosaic
    takes the shape (the largest yes shapes here); the no cases are ones
    the compiler does refuse — whole-M x whole-O tiles outgrow VMEM."""
    from paddle_tpu.ops.pallas.fused_dequant_matmul import (
        fused_dequant_matmul_is_supported)
    assert fused_dequant_matmul_is_supported(m, k, o) is says
    fn, args = _dequant(m, k, o)
    if says:
        _compile(fn, *args)
    else:
        with pytest.raises(Exception, match="vmem"):
            jax.jit(fn).lower(*args).compile()
