"""Pipeline-parallel execution. Parity:
python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py ::
PipelineParallel.train_batch (1F1B), PipelineParallelWithInterleave
(+ pp_utils/p2p_communication.py SendRecvMeta handshake).

TPU-native execution model: there are no per-stage OS processes or NCCL P2P
queues. When the hybrid mesh has pp ≥ 2 and the PipelineLayer's middle is a
PERIODIC layer stack (homogeneous period-1 transformers, or period-k
patterns like MoE-every-k / wide-narrow alternations), `train_batch`
compiles the WHOLE schedule into one SPMD program: the
stage bodies are stacked on a leading pp axis, `shard_map` places one stage
per pp rank, and the `lax.scan`-of-`ppermute` engine in
paddle_tpu.parallel.pipeline runs the micro-batch schedule (GPipe fill-drain;
interleaved virtual chunks for PipelineParallelWithInterleave). Activation
passing is the ppermute ICI neighbor exchange — shapes are static under jit
so there is no SendRecvMeta handshake to replicate. Embedding/head layers
outside the homogeneous run execute under GSPMD (replicated over pp, sharded
over mp/dp per their annotations) before/after the pipelined section.

Fallback (no mesh, pp == 1, or a body with no usable periodic run): the
reference's
micro-batch loop — split into accumulate_steps micro-batches,
forward/backward each, accumulate grads, one optimizer step — which is
numerically identical to 1F1B.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ....tensor.tensor import Tensor, no_grad, _tape
from .parallel_layers import MetaParallelBase
from .pp_layers import PipelineLayer

__all__ = ["PipelineParallel", "PipelineParallelWithInterleave"]


class _NotPipelineable(Exception):
    pass


def _param_sig(layer):
    """Structural identity for 'same stage body' detection: class (the
    forward fn) + parameter shapes/dtypes. Param shapes alone are not
    enough — a stem Linear and a residual block can share shapes. For
    PARAM-LESS layers the class name alone is not enough either: two
    _FnLayers wrapping different callables (relu vs silu) or two Dropouts
    with different rates would collide and chunk_apply would silently run
    the template's behavior for both — so include config scalars and the
    wrapped-callable identity (distinct lambdas never match: conservative
    by construction)."""
    params = tuple((tuple(p.shape), str(p.dtype))
                   for p in layer.parameters())
    # ``_scope`` is the name the layer has in its parent (its index in the
    # stack): where it sits, not what it computes
    cfg = tuple(sorted((k, str(v)) for k, v in vars(layer).items()
                       if isinstance(v, (int, float, bool, str))
                       and k != "_scope"))
    fn = getattr(layer, "_fn", None)
    # cfg applies to PARAM-BEARING layers too: same class + same shapes but
    # a different behavior flag (e.g. act='relu' vs 'gelu') must not match,
    # or chunk_apply would run the template's forward for both positions
    return (type(layer).__qualname__, params, cfg,
            None if fn is None else id(fn))


def _find_body(layers, slots):
    """Longest run of consecutive layers whose parameter-signature sequence
    is PERIODIC (period k ≤ 4; k=1 is the homogeneous case), usable length
    a multiple of slots·k so every stage holds whole patterns
    (slots = pp_degree · virtual chunks). Periodic bodies cover the
    reference's non-uniform stacks — MoE-every-k blocks, Linear/Activation
    alternations — that a strict homogeneity test would reject.
    Returns (start, end, period)."""
    sigs = [_param_sig(l) for l in layers]
    n = len(layers)
    best = None          # (usable_len, -period, start)
    for k in (1, 2, 3, 4):
        i = 0
        while i < n:
            j = i + k
            while j < n and sigs[j] == sigs[j - k]:
                j += 1
            run = j - i
            unit = slots * k
            usable = (run // unit) * unit
            # at least one position must carry params (something to stack)
            if usable >= unit and any(sigs[i + t][1] for t in range(k)):
                cand = (usable, -k, i)
                if best is None or cand > best:
                    best = cand
            i = i + 1 if run < unit else j
    if best is None:
        raise _NotPipelineable(
            f"no periodic layer run of length divisible by {slots}")
    usable, neg_k, start = best
    return start, start + usable, -neg_k


def _substitute(params, arrays):
    old = [p._data for p in params]
    for p, a in zip(params, arrays):
        p._data = a
    return old


def _layer_params(layer):
    """Layer params INCLUDING tied weights hidden behind _SharedForward's
    unregistered reference (pp_layers keeps it out of parameters() to avoid
    double registration — but the jit step must receive the shared weight
    as an argument, not bake it in as a trace-time constant)."""
    ref = getattr(layer, "_shared_layer_ref", None)
    if ref:
        return list(ref[0].parameters())
    return list(layer.parameters())


def _apply_seq(layers, x):
    """Apply a layer sequence (params already substituted by the caller).
    x: raw array (or tuple of Tensors) -> raw array."""
    h = x if isinstance(x, tuple) else Tensor(x)
    with no_grad():
        for lay in layers:
            h = lay(*h) if isinstance(h, tuple) else lay(h)
    return h._data if isinstance(h, Tensor) else h


class PipelineParallel(MetaParallelBase):
    def __init__(self, layers, hcg, strategy):
        super().__init__(layers, hcg, strategy)
        pp_cfg = strategy.hybrid_configs.get("pp_configs", {}) if strategy else {}
        self.accumulate_steps = (
            pp_cfg.get("accumulate_steps", 1) if hasattr(pp_cfg, "get") else 1)
        self.micro_batch_size = (
            pp_cfg.get("micro_batch_size", 1) if hasattr(pp_cfg, "get") else 1)
        # remat window for the compiled schedule (gpipe block checkpointing);
        # "auto" = sqrt(T), None = store every tick input (faster backward)
        self.remat_window = (
            pp_cfg.get("remat_window", "auto") if hasattr(pp_cfg, "get")
            else "auto")
        self.num_stages = hcg.get_pipe_parallel_world_size()
        self.stage_id = hcg.get_stage_id()
        self.num_virtual = 1
        self.total_loss = None
        self._pp_cache = {}

    def is_pipeline_first_stage(self):
        return self.stage_id == 0

    def is_pipeline_last_stage(self):
        return self.stage_id == self.num_stages - 1

    # ------------------------------------------------------------ compiled pp
    def _mesh(self):
        mesh = getattr(self._hcg, "mesh", None)
        if mesh is not None and dict(mesh.shape).get("pp", 1) >= 2:
            return mesh
        return None

    def _partition(self):
        """Split run_function into (prologue, body, epilogue, period); the
        body is the periodic stack that gets pipelined over pp (round-robin
        chunked for virtual pp)."""
        layers = list(self._layers.run_function)
        slots = self.num_stages * self.num_virtual
        b0, b1, period = _find_body(layers, slots)
        return layers[:b0], layers[b0:b1], layers[b1:], period

    def _build_step(self, mesh, key):
        from ....parallel.pipeline import (gpipe, gpipe_interleaved,
                                           microbatch, unmicrobatch)
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        pro, body, epi, period = self._partition()
        pp, v = self.num_stages, self.num_virtual
        lc = len(body) // (pp * v)          # layers per chunk (k | lc)
        reps = lc // period                 # pattern repeats per chunk
        templates = body[:period]           # one live layer per position
        tpar = [list(t.parameters()) for t in templates]
        # every param the prologue/epilogue touch — including tied weights
        # reached via _SharedForward — deduped so each Parameter is exactly
        # one jit argument (a tied weight used in both gets one grad slot
        # covering both uses); body params travel separately as the stacked
        # pp-sharded argument
        body_ids = {id(p) for lay in body for p in lay.parameters()}
        seq_params, seen = [], set()
        for lay in list(pro) + list(epi):
            for p in _layer_params(lay):
                if id(p) not in seen and id(p) not in body_ids:
                    seen.add(id(p))
                    seq_params.append(p)
        model = self._layers
        micro = self.accumulate_steps
        data_axes = tuple(a for a in ("dp", "sharding") if a in mesh.shape)

        def stack_body():
            """Per pattern-position t, per param k: stacks of the layers at
            that position -> [P, v, reps, ...]. Global chunk g = c·P + i
            (reference round-robin) holds layers [g·Lc, (g+1)·Lc); since
            period | Lc, layer index i has position i % period."""
            out = []
            for t in range(period):
                pos_layers = body[t::period]
                pos = []
                for k in range(len(tpar[t])):
                    a = jnp.stack([lay.parameters()[k]._data
                                   for lay in pos_layers])
                    a = a.reshape(v, pp, reps, *a.shape[1:])
                    pos.append(jnp.moveaxis(a, 1, 0))
                out.append(pos)
            return out

        def chunk_apply(chunk_arrays, h):
            # chunk_arrays: [position][param] leaves with leading `reps`
            def one(h, rep_arrays):
                for t, template in enumerate(templates):
                    old = _substitute(tpar[t], rep_arrays[t])
                    try:
                        with no_grad():
                            h = template(Tensor(h))._data
                    finally:
                        _substitute(tpar[t], old)
                return h, None
            h, _ = jax.lax.scan(one, h, chunk_arrays)
            return h

        # shard the micro-batch dim over the data axes only when it divides
        # (else replicate — correct, just less parallel)
        data_world = 1
        for a in data_axes:
            data_world *= mesh.shape[a]
        mb_size = key[0][0] // max(micro, 1)
        shard_mb = bool(data_axes) and data_world > 1 and \
            mb_size % data_world == 0

        # HYBRID COMPOSITION (mp×pp×sharding in ONE program): only the pp
        # axis is manual (ppermute schedule); mp/sharding/dp stay GSPMD-
        # auto inside the shard_map, so the TP layers' sharding constraints
        # keep working inside stage bodies and the body params keep their
        # at-rest specs ('mp' from Column/RowParallel, 'sharding' from
        # stage 3) — XLA inserts the per-use all-gathers and the grad
        # reduce-scatters the reference's GroupShardedStage3 hooks code by
        # hand. Stacked body param k of pattern position t is
        # [P, v, reps, *shape]: P consumed by the manual pp spec,
        # [v, reps] replicated, then the param's own spec.
        def _stacked_spec(p):
            from ....parallel import _valid_spec
            sp = getattr(p, "sharding_spec", None)
            if sp is None or not _valid_spec(p._data, sp, mesh):
                return None
            return P(None, None, *sp)
        stacked_specs = [[_stacked_spec(p) for p in pos] for pos in tpar]

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P("pp"), P()), out_specs=P(),
                           axis_names={"pp"}, check_vma=False)
        def run_pipe(stacked, h_mb):
            # bare PartitionSpecs bind to the CONTEXT mesh (pp is Manual
            # inside this shard_map) — a concrete-mesh NamedSharding here
            # would mismatch axis types and fail to trace
            local = jax.tree.map(lambda a: a[0], stacked)   # [v, reps, ...]
            local = [
                [a if sp is None else
                 jax.lax.with_sharding_constraint(a, sp)
                 for a, sp in zip(pos, pos_specs)]
                for pos, pos_specs in zip(local, stacked_specs)]
            if shard_mb:
                h_mb = jax.lax.with_sharding_constraint(
                    h_mb, P(None, data_axes,
                            *([None] * (h_mb.ndim - 2))))
            if v == 1:
                local = jax.tree.map(lambda a: a[0], local)
                return gpipe(chunk_apply, local, h_mb,
                             window=self.remat_window)
            return gpipe_interleaved(chunk_apply, local, h_mb, num_chunks=v)

        from ....nn.layer.layers import substitute_param_arrays

        def pure_step(seq_arrays, stacked, x, y, scale):
            _tape.nodes.clear()
            with substitute_param_arrays(seq_params, seq_arrays):
                h = _apply_seq(pro, x)
                h_mb = microbatch(h, micro)
                out = unmicrobatch(run_pipe(stacked, h_mb))
                out = _apply_seq(epi, out)
                with no_grad():
                    loss = model.loss(Tensor(out),
                                      None if y is None else Tensor(y))
            loss = loss._data if isinstance(loss, Tensor) else loss
            loss = jnp.mean(loss)
            _tape.nodes.clear()
            return loss * scale, loss

        grad_fn = jax.jit(jax.value_and_grad(pure_step, argnums=(0, 1),
                                             has_aux=True))
        self._pp_cache[key] = (grad_fn, stack_body, seq_params, body, period)
        return self._pp_cache[key]

    def _compiled_pipeline(self, x, y, scaler):
        mesh = self._mesh()
        x_arr = x._data if isinstance(x, Tensor) else jnp.asarray(x)
        y_arr = None if y is None else (
            y._data if isinstance(y, Tensor) else jnp.asarray(y))
        if x_arr.shape[0] % max(self.accumulate_steps, 1) != 0:
            raise _NotPipelineable("batch not divisible by accumulate_steps")
        key = (tuple(x_arr.shape), str(x_arr.dtype),
               None if y_arr is None else tuple(y_arr.shape))
        entry = self._pp_cache.get(key) or self._build_step(mesh, key)
        grad_fn, stack_body, seq_params, body, period = entry

        scale = jnp.asarray(1.0 if scaler is None else scaler._scale,
                            jnp.float32)
        seq_arrays = [p._data for p in seq_params]
        stacked = stack_body()
        (_, loss), (g_seq, g_stack) = grad_fn(
            seq_arrays, stacked, x_arr, y_arr, scale)

        def add_grad(p, g):
            g = g.astype(p._data.dtype)
            p.grad = Tensor(g) if p.grad is None else Tensor(p.grad._data + g)

        for p, g in zip(seq_params, g_seq):
            add_grad(p, g)
        pp, v = self.num_stages, self.num_virtual
        lc = len(body) // (pp * v)
        reps = lc // period
        for t in range(period):
            pos_layers = body[t::period]    # ordered (chunk g, repeat r)
            for k, gs in enumerate(g_stack[t]):
                # [P, v, reps, ...] -> [g·reps + r, ...] inverse of stack
                flat = jnp.moveaxis(gs, 0, 1).reshape(pp * v * reps,
                                                      *gs.shape[3:])
                for li, lay in enumerate(pos_layers):
                    add_grad(lay.parameters()[k], flat[li])
        self._pp_cache["_ran"] = True
        return Tensor(loss)

    # ------------------------------------------------------------- schedules
    def _split_micro(self, data):
        if isinstance(data, (list, tuple)):
            xs = [self._split_micro(d) for d in data]
            return list(zip(*xs))
        n = self.accumulate_steps
        b = data.shape[0]
        mb = max(b // n, 1)
        return [data[i * mb:(i + 1) * mb] for i in range(min(n, b // mb))]

    def forward_backward_pipeline(self, data, scaler=None):
        if isinstance(data, (list, tuple)) and len(data) == 2:
            x, label = data
        else:
            x, label = data, None
        if self._mesh() is not None and isinstance(self._layers,
                                                   PipelineLayer) and \
                not getattr(self, "_pp_disabled", False):
            try:
                self.total_loss = self._compiled_pipeline(x, label, scaler)
                return self.total_loss
            except _NotPipelineable:
                pass
            except Exception as e:
                if self._pp_cache.get("_ran"):
                    raise  # steady-state failure is a real error — surface it
                # first build/trace failed (e.g. tuple inter-stage
                # activations the compiled engine doesn't handle yet):
                # fall back to the numerically-identical micro-batch loop
                import warnings
                warnings.warn(
                    f"pipeline compile failed ({type(e).__name__}: {e}); "
                    f"falling back to sequential micro-batch schedule")
                self._pp_disabled = True
        model = self._layers
        micro_batches = self._split_micro(data)
        total = None
        n = len(micro_batches)
        for mb in micro_batches:
            if isinstance(mb, (list, tuple)) and len(mb) == 2:
                x, label = mb
            else:
                x, label = mb, None
            out = model(x) if not isinstance(model, PipelineLayer) else \
                model.forward(x)
            loss = model.loss(out, label) if isinstance(model, PipelineLayer) \
                else out
            scaled = loss / n
            if scaler is not None:
                scaled = scaler.scale(scaled)
            scaled.backward()
            total = loss.detach() if total is None else total + loss.detach()
        self.total_loss = total / n
        return self.total_loss

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        self._layers.train()
        loss = self.forward_backward_pipeline(data, scaler)
        if scaler is not None:
            scaler.step(optimizer)
            scaler.update()
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        return loss

    @no_grad()
    def eval_batch(self, data, compute_loss=True):
        self._layers.eval()
        micro_batches = self._split_micro(data)
        total = None
        for mb in micro_batches:
            if isinstance(mb, (list, tuple)) and len(mb) == 2:
                x, label = mb
            else:
                x, label = mb, None
            model = self._layers
            out = model(x)
            loss = model.loss(out, label) if isinstance(model, PipelineLayer) \
                and compute_loss else out
            total = loss.detach() if total is None else total + loss.detach()
        return total / max(len(micro_batches), 1)


class PipelineParallelWithInterleave(PipelineParallel):
    """Interleaved (virtual-pipeline) schedule: layers assigned to stages
    round-robin in chunks; executed by
    parallel.pipeline.gpipe_interleaved's wave schedule (bubble P-1 vs the
    sequential v·(P-1))."""

    def __init__(self, layers, hcg, strategy):
        super().__init__(layers, hcg, strategy)
        self.num_virtual = max(
            int(getattr(layers, "_num_virtual_pipeline_stages", None) or 2), 1)
