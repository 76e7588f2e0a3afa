"""Pipeline-parallel execution engine over a named "pp" mesh axis.

Capability parity: python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py :: PipelineParallel.train_batch (micro-batch 1F1B) and
pp_utils/p2p_communication.py (stage-to-stage activation passing).

TPU-native design (NOT a port): the reference runs one OS process per stage
and hand-schedules NCCL P2P send/recv. Here every stage is a mesh
coordinate; one SPMD program executes the whole schedule inside a
`lax.scan` of M + P - 1 ticks, with `ppermute` moving activations to the
next stage each tick (the ICI neighbor exchange). Backward falls out of
`jax.grad` through the scan — the reverse-mode schedule is exactly the
pipeline backward pass, and per-tick `jax.checkpoint` gives the 1F1B-class
activation-memory profile (store only stage inputs, recompute inside).
XLA's latency-hiding scheduler overlaps each ppermute with the next tick's
compute; there is no TCPStore/SendRecvMeta machinery to replicate because
shapes are static under jit.

Usage (see tests/test_pipeline_engine.py):
    mesh = Mesh(devs, ("pp",))
    fn = make_gpipe_fn(stage_fn, mesh)   # stage_fn(stage_params, h) -> h
    out = fn(stacked_params, microbatches)     # params: [P, ...] pp-sharded
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe", "gpipe_interleaved", "make_gpipe_fn", "microbatch",
           "unmicrobatch"]


def _pvary(x, axis_name):
    """Mark x as varying over axis_name."""
    return jax.lax.pcast(x, (axis_name,), to="varying")


def microbatch(x, num_micro: int):
    """[B, ...] -> [M, B/M, ...]."""
    b = x.shape[0]
    assert b % num_micro == 0, f"batch {b} not divisible by {num_micro}"
    return x.reshape(num_micro, b // num_micro, *x.shape[1:])


def unmicrobatch(x):
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def gpipe(stage_fn: Callable, stage_params, x_mb, axis_name: str = "pp",
          remat: bool = True, window: int | str | None = "auto"):
    """Run the micro-batch pipeline schedule; call inside shard_map.

    stage_fn(stage_params, h) -> h : applies ONE stage's layers (an inner
        lax.scan over the stage's layer slice for multi-layer stages).
    stage_params: this device's stage slice (leading stage axis removed).
    x_mb: [M, mb, ...] microbatched stage-0 input (replicated over pp).
    Returns [M, mb, ...] final-stage outputs, identical on every pp rank.

    Activation memory (the 1F1B-class bound the reference's schedule
    exists for): ticks are grouped into `window`-sized blocks, each under
    one jax.checkpoint — backward stores only the BLOCK-BOUNDARY carries
    (one microbatch activation each) and replays a block's ticks when its
    grads are needed. Stored boundary activations = T/W + W peak
    (T = M+P-1 ticks), minimized at W=√T ("auto"). Recompute cost is ≤2
    extra forwards: one for the block replay, one for the per-tick remat
    that stays ON inside blocks so a replayed block holds W tick INPUTS
    rather than W ticks' full within-stage intermediates (for multi-layer
    stages the latter dominates peak memory). The outputs bank leaves the
    scan carry entirely: every tick emits its state as a scan output and
    the last-stage outputs are the contiguous tick slice [P-1, P-1+M) — a
    linear gather that saves no residuals. window=None disables blocking
    (single scan, per-tick remat only); remat=False disables BOTH remat
    levels unless `window` is explicitly set to an int.
    """
    p = jax.lax.axis_size(axis_name)
    i = jax.lax.axis_index(axis_name)
    m = x_mb.shape[0]
    total = m + p - 1
    perm = [(j, (j + 1) % p) for j in range(p)]

    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    state0 = _pvary(jnp.zeros_like(x_mb[0]), axis_name)

    def tick(state, t):
        incoming = jax.lax.ppermute(state, axis_name, perm)
        mb = jax.lax.dynamic_index_in_dim(x_mb, jnp.minimum(t, m - 1), 0,
                                          keepdims=False)
        inp = jnp.where(i == 0, mb, incoming)
        new = fn(stage_params, inp)
        return new, new

    if window == "auto":
        # remat=False means "spend memory for backward speed" — don't
        # silently reintroduce recompute via the block checkpoint
        window = None if not remat else \
            max(int(np.ceil(np.sqrt(total))), 1)
    if window and 1 < window < total:
        n_win = -(-total // window)           # ceil; tail ticks padded
        ts = jnp.arange(n_win * window).reshape(n_win, window)

        @jax.checkpoint
        def run_window(state, t_block):
            return jax.lax.scan(tick, state, t_block)

        _, ys = jax.lax.scan(run_window, state0, ts)
        ys = ys.reshape(n_win * window, *ys.shape[2:])
    else:
        _, ys = jax.lax.scan(tick, state0, jnp.arange(total))
    # device p-1's tick t ≥ p-1 completed microbatch t-(p-1)
    outs = jax.lax.slice_in_dim(ys, p - 1, p - 1 + m, axis=0)
    # broadcast the final-stage outputs to every rank (loss is computed
    # replicated, exactly like the reference's shared-loss broadcast)
    outs = jnp.where(i == p - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs, axis_name)


def gpipe_interleaved(stage_fn: Callable, chunk_params, x_mb,
                      axis_name: str = "pp", num_chunks: int = 2,
                      remat: bool = True):
    """Interleaved (virtual-pipeline) schedule; call inside shard_map.

    Parity: PipelineParallelWithInterleave (virtual_pp_degree model chunks
    per rank). Layer assignment is the reference's round-robin: of the
    v·P chunks in layer order, stage i holds chunks {i, P+i, 2P+i, ...}.

    TPU-native schedule (single SPMD scan, no P2P processes): microbatches
    are processed in depth-first waves of P. Device 0's emission clock τ
    advances one slot per tick; slot τ of wave w (u = τ - w·v·P) carries
    microbatch m = w·P + u%P at chunk c = u//P. An activation finishing
    chunk c on device P-1 re-enters device 0 exactly when the schedule
    processes (m, c+1) there, so no rank ever buffers more than the one
    in-flight activation — the per-device chunk select is a
    dynamic_index over the local [v, ...] chunk stack. Pipeline bubble is
    P-1 ticks total (vs v·(P-1) for running v sequential gpipe passes),
    matching the interleaved-1F1B bubble reduction. M not divisible by P
    leaves masked tail slots in the last wave; their TICKS are irreducible
    (ring latency), but their compute is skipped via lax.cond in the tick.

    chunk_params: this device's chunks, leading axis v (chunk c = global
        chunk c·P + i). stage_fn(one_chunk_params, h) -> h.
    x_mb: [M, mb, ...] microbatched input, replicated over pp.
    Returns [M, mb, ...] final outputs, identical on every pp rank.
    """
    p = jax.lax.axis_size(axis_name)
    i = jax.lax.axis_index(axis_name)
    m = x_mb.shape[0]
    v = num_chunks
    waves = -(-m // p)                      # ceil
    total = waves * v * p + p - 1
    perm = [(j, (j + 1) % p) for j in range(p)]

    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    state0 = _pvary(jnp.zeros_like(x_mb[0]), axis_name)
    outs0 = _pvary(jnp.zeros_like(x_mb), axis_name)

    def tick(carry, t):
        state, outs = carry
        incoming = jax.lax.ppermute(state, axis_name, perm)
        tau = t - i                          # device-0 emission clock
        w = tau // (v * p)
        u = tau - w * (v * p)
        c = jnp.clip(u // p, 0, v - 1)
        mb_idx = jnp.clip(w * p + u % p, 0, m - 1)
        valid = (tau >= 0) & (tau < waves * v * p) & (w * p + u % p < m)

        inject = (i == 0) & (c == 0)
        mb = jax.lax.dynamic_index_in_dim(x_mb, mb_idx, 0, keepdims=False)
        inp = jnp.where(inject, mb, incoming)
        params_c = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
            chunk_params)
        # Invalid slots (ramp-up/down + the masked tail when M % P != 0)
        # SKIP the stage compute entirely — a real lax.cond, not a select:
        # inside shard_map the predicate is a per-device scalar, so the
        # false branch is a true no-op that passes the ring value through
        # instead of computing garbage and discarding it. The tail TICKS
        # themselves are irreducible: a chunk wave must span P ticks
        # because that is the ring latency before (mb, c+1) can re-enter
        # device 0, so a "shorter last wave" would ask for activations
        # that have not completed the ring yet.
        new = jax.lax.cond(valid, lambda: fn(params_c, inp),
                           lambda: incoming)

        done = (i == p - 1) & (c == v - 1) & valid
        cur = jax.lax.dynamic_index_in_dim(outs, mb_idx, 0, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(done, new, cur), mb_idx, 0)
        return (new, outs), None

    (_, outs), _ = jax.lax.scan(tick, (state0, outs0), jnp.arange(total))
    outs = jnp.where(i == p - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs, axis_name)


def make_gpipe_fn(stage_fn: Callable, mesh: Mesh, axis_name: str = "pp",
                  remat: bool = True, num_micro: int | None = None,
                  window: int | str | None = "auto"):
    """Global-view pipeline: params [P, ...] sharded over the pp axis,
    x either [M, mb, ...] pre-microbatched or [B, ...] with num_micro set.
    Returns full-batch outputs replicated over pp. jit-compatible.
    `window` passes through to gpipe (block-checkpoint size; None trades
    memory for backward speed)."""

    pspec = P(axis_name)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P())
    def run(stacked_params, x_mb):
        local = jax.tree.map(lambda a: a[0], stacked_params)
        out = gpipe(stage_fn, local, x_mb, axis_name=axis_name, remat=remat,
                    window=window)
        return out

    def fn(stacked_params, x):
        x_mb = x if num_micro is None else microbatch(x, num_micro)
        out = run(stacked_params, x_mb)
        return out if num_micro is None else unmicrobatch(out)

    return fn
