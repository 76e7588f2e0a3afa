"""Optimizer base + SGD/Momentum/Adam/AdamW/Adagrad/RMSProp/Lamb.

Parity: python/paddle/optimizer/{optimizer,adamw,adam,momentum,sgd,lamb}.py and
the fused AdamW Phi kernel (paddle/phi/kernels/gpu/adamw_kernel.cu ::
AdamwDenseKernel, multi_tensor_adam). TPU-first: updates are pure jnp
expressions; under paddle.jit.to_static the whole param-loop compiles into one
XLA program, which IS the multi-tensor fused form. Supports multi_precision
(bf16 params with fp32 master weights) as in AMP-O2.
"""
from __future__ import annotations

from typing import Iterable, Optional

import jax
import jax.numpy as jnp

from ..tensor.tensor import (PASS_OPTIMIZER, Parameter, Tensor, no_grad,
                             register_persistent)
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adafactor",
           "Adagrad", "Adadelta", "RMSProp", "Lamb", "Adamax", "NAdam",
           "RAdam", "ASGD", "Rprop"]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        self._lr = learning_rate
        self._parameter_list = list(parameters) if parameters is not None else None
        self._param_groups = None
        if self._parameter_list and isinstance(self._parameter_list[0], dict):
            self._param_groups = self._parameter_list
            flat = []
            for g in self._param_groups:
                flat.extend(g["params"])
            self._parameter_list = flat
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators: dict[str, dict[int, Tensor]] = {}
        self._master_weights: dict[int, Tensor] = {}
        self._step_count = 0
        if self._parameter_list:
            # plain trainable Tensors must live in the persistent registry
            # too: jit.to_static functionalizes persistent state, and an
            # optimizer-updated tensor outside it would trap a tracer
            for p in self._parameter_list:
                if isinstance(p, Tensor):
                    register_persistent(p)

    # ----------------------------------------------------------------- lr
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return self._lr()
        return float(self._lr)

    def set_lr(self, value: float):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    def set_lr_scheduler(self, scheduler):
        self._lr = scheduler

    @property
    def _learning_rate(self):
        return self._lr

    # --------------------------------------------------------- accumulators
    def _acc(self, name: str, p: Parameter, init=None) -> Tensor:
        slot = self._accumulators.setdefault(name, {})
        key = id(p)
        if key in slot and slot[key]._data is None:
            del slot[key]  # dead slot: failed-trace rollback invalidated it
        if key not in slot:
            if init is None:
                arr = jnp.zeros_like(self._master(p)._data)
            else:  # callable init is lazy: only evaluated on first use
                arr = init() if callable(init) else init
            t = Tensor(arr)
            t.persistable = True
            t.name = f"{p.name}_{name}"
            register_persistent(t)
            slot[key] = t
        return slot[key]

    def _seed_master(self, p: Parameter, value) -> Tensor:
        """Create + register the fp32 master slot for ``p`` from ``value``
        (idempotent). The static AMP pass seeds from the pre-cast fp32
        weights; the lazy path below seeds from the current values."""
        key = id(p)
        if (key in self._master_weights
                and self._master_weights[key]._data is None):
            del self._master_weights[key]  # dead: failed-trace rollback
        if key not in self._master_weights:
            t = Tensor(jnp.asarray(value).astype(jnp.float32))
            t.persistable = True
            t.name = f"{p.name}_master"
            register_persistent(t)
            self._master_weights[key] = t
        return self._master_weights[key]

    def _master(self, p: Parameter) -> Tensor:
        """fp32 master weight when multi_precision and p is low-precision."""
        if not self._multi_precision or p.dtype == jnp.float32:
            return p
        return self._seed_master(p, p._data)

    def _params(self) -> list[Parameter]:
        if self._parameter_list is not None:
            return self._parameter_list
        from ..tensor.tensor import persistent_tensors
        return [t for t in persistent_tensors()
                if isinstance(t, Parameter) and t.trainable]

    # ----------------------------------------------------------------- step
    def step(self):
        # robustness hooks on the train-step path: surface a watchdog-
        # detected peer failure as PeerFailureError at the step boundary
        # (instead of entering a doomed collective), and give the fault-
        # injection harness its per-step trigger point. Both are ~free
        # when the watchdog is off / the harness is disarmed.
        from ..distributed.resilience import (check_peer_failure,
                                              notify_progress)
        from ..testing import fault
        check_peer_failure()
        notify_progress()
        fault.inject("step")
        with no_grad():
            # plain Tensors (stop_gradient=False) are optimizable too —
            # the reference accepts any trainable tensor, not just
            # Parameters (python/paddle/optimizer/optimizer.py)
            params_grads = [
                (p, p.grad) for p in self._params()
                if getattr(p, "trainable", not p.stop_gradient)
                and p.grad is not None]
            lr = self.get_lr()
            self._step_count += 1
            if self._should_fuse(params_grads):
                try:
                    self._fused_eager_step(params_grads, lr)
                    return
                except Exception as e:
                    import warnings
                    warnings.warn(
                        f"fused eager optimizer step failed "
                        f"({type(e).__name__}: {e}); falling back to the "
                        f"per-param loop")
                    self._fuse_eager = False     # sticky disable
                    self._purge_tracer_slots()   # drop half-built slots
            self._step_core(params_grads, lr)

    def _purge_tracer_slots(self):
        """A fused trace that failed after lazily creating accumulator/
        master slots leaves them holding escaped tracers — drop those so
        the eager fallback (and every later to_static call) sees only
        concrete state."""
        import jax

        def dead(t):
            # tracer = escaped from this (fused-eager) failure path;
            # None = already killed by the jit failed-trace rollback
            return t._data is None or isinstance(t._data, jax.core.Tracer)

        for slot in self._accumulators.values():
            for k in [k for k, t in slot.items() if dead(t)]:
                del slot[k]
        for k in [k for k, t in self._master_weights.items()
                  if dead(self._master_weights[k])]:
            del self._master_weights[k]

    def _step_core(self, params_grads, lr):
        # the pass marker of a compiled step's optimizer operations (the
        # clip, the update, the master-weight cast), then which of them
        with jax.named_scope(PASS_OPTIMIZER):
            if self._grad_clip is not None:
                with jax.named_scope("clip"):
                    params_grads = self._grad_clip(params_grads)
            with jax.named_scope(type(self).__name__.lower()):
                for p, g in params_grads:
                    # per-param lr scaling from ParamAttr(learning_rate=...)
                    scale = getattr(p, "optimize_attr", None)
                    p_lr = lr * scale["learning_rate"] if scale else lr
                    self._update_param(p, g, p_lr)

    def _should_fuse(self, params_grads) -> bool:
        """Fuse the EAGER step into one compiled program (the reference's
        multi_tensor_adam: one kernel over all params instead of a
        per-param dispatch storm). Inside an outer to_static trace the
        step is already being compiled — run inline there."""
        import jax
        if getattr(self, "_fuse_eager", None) is None:
            # tri-state: None = read env once; False stays sticky after a
            # fallback so a deterministic failure doesn't retrace forever
            import os
            self._fuse_eager = os.environ.get(
                "PADDLE_TPU_FUSE_EAGER_STEP", "1") != "0"
        return bool(self._fuse_eager and params_grads
                    and not isinstance(params_grads[0][0]._data,
                                       jax.core.Tracer)
                    and not isinstance(params_grads[0][1]._data,
                                       jax.core.Tracer))

    def _fused_eager_step(self, params_grads, lr):
        """One jitted program per param-set: grads + lr travel as
        arguments (no retrace when the scheduler moves the lr); state
        writes functionalize through to_static's persistent-state
        machinery, exactly like a compiled train step."""
        key = (tuple(id(p) for p, _ in params_grads), self._hyper_key(
            [p for p, _ in params_grads]))
        cache = getattr(self, "_fused_cache", None)
        if cache is None:
            cache = self._fused_cache = {}
        if len(cache) == 8 and key not in cache:
            # cache-size guard (r3 weak #8): a churning key means some
            # Python-level hyperparameter (clip config, wd groups, per-
            # param lr scales) mutates every step — each step then pays a
            # full retrace. Warn once; keep stepping correctly.
            import warnings
            warnings.warn(
                "fused eager step: 9th distinct (param-set, hyperparam) "
                "signature — per-step hyperparameter churn causes a "
                "retrace every step; set PADDLE_TPU_FUSE_EAGER_STEP=0 or "
                "hold hyperparameters constant between steps",
                UserWarning, stacklevel=3)
        if len(cache) >= 16 and key not in cache:
            # bound host memory under churn: evict the oldest compiled
            # program (insertion order); warn-once above already fired
            del cache[next(iter(cache))]
        fn = cache.get(key)
        if fn is None:
            from ..jit import to_static
            params = [p for p, _ in params_grads]

            def run(grads, lr_t):
                self._step_core(list(zip(params, grads)), lr_t._data)
                return Tensor(jnp.zeros((), jnp.float32))
            # not donated: an eager opt.step() does not own the state as a
            # compiled step does; the tape and user code may still hold the
            # parameter arrays of the forward pass behind these gradients
            fn = cache[key] = to_static(run, donate_state=False)
            self._fused_fn = fn          # introspection/debug handle
        fn([g for _, g in params_grads],
           Tensor(jnp.asarray(lr, jnp.float32)))

    def _hyper_key(self, params):
        """Python-level hyperparameters the trace bakes in as constants —
        part of the cache key so mutating them mid-training retraces
        instead of silently keeping stale values (the eager loop re-read
        them every step)."""
        clip = self._grad_clip
        clip_sig = None if clip is None else (
            type(clip).__name__,
            getattr(clip, "clip_norm", None), getattr(clip, "max", None),
            getattr(clip, "min", None), getattr(clip, "clip_value", None))
        lr_scales = tuple(
            (getattr(p, "optimize_attr", None) or {}).get(
                "learning_rate", 1.0) for p in params)
        return (clip_sig, getattr(self, "_wd_coeff", None),
                self._weight_decay if isinstance(self._weight_decay,
                                                 (int, float)) else None,
                lr_scales)

    def _update_param(self, p: Parameter, g: Tensor, lr: float):
        raise NotImplementedError

    def _apply(self, p: Parameter, new_master_value):
        """Write updated fp32 value back to master + model param."""
        m = self._master(p)
        if m is not p:
            m._data = new_master_value
            p._data = new_master_value.astype(p.dtype)
        else:
            p._data = new_master_value.astype(p.dtype)

    def _decayed(self, p, g32, m32):
        """L2-regularizer-style weight decay folded into the gradient
        (Paddle's `weight_decay=L2Decay(...)` semantics for non-AdamW)."""
        # per-param ParamAttr regularizer overrides the optimizer-level one
        # (reference precedence: python/paddle/regularizer.py docstring)
        reg = getattr(p, "regularizer", None)
        wd = self._weight_decay if reg is None else reg
        if wd is None:
            return g32
        reg = wd
        if callable(reg) and not isinstance(reg, float):
            return reg(g32, m32)
        coeff = getattr(reg, "_coeff",
                        getattr(reg, "coeff",
                                reg if isinstance(reg, float) else 0.0))
        return g32 + coeff * m32

    def clear_grad(self, set_to_zero: bool = False):
        for p in self._params():
            p.clear_gradient(set_to_zero)
    clear_gradients = clear_grad

    # ------------------------------------------------------------- state io
    def state_dict(self) -> dict:
        sd: dict = {}
        params = {id(p): name_of(p) for p in self._params()}
        # skip dead slots (_data=None): a failed-trace rollback killed them
        # before they ever held a value — they are semantically absent
        for acc_name, slot in self._accumulators.items():
            for pid, t in slot.items():
                if t._data is not None:
                    sd[f"{params.get(pid, pid)}_{acc_name}"] = t
        for pid, t in self._master_weights.items():
            if t._data is not None:
                sd[f"{params.get(pid, pid)}_master"] = t
        if isinstance(self._lr, LRScheduler):
            sd["LR_Scheduler"] = self._lr.state_dict()
        sd["@step"] = self._step_count
        return sd

    def set_state_dict(self, state_dict: dict):
        params = {name_of(p): p for p in self._params()}
        self._step_count = int(state_dict.get("@step", 0))
        if "LR_Scheduler" in state_dict and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state_dict["LR_Scheduler"])
        for key, val in state_dict.items():
            if key in ("LR_Scheduler", "@step"):
                continue
            for pname, p in params.items():
                if not key.startswith(pname + "_"):
                    continue
                suffix = key[len(pname) + 1:]
                arr = val._data if isinstance(val, Tensor) else jnp.asarray(val)
                if suffix == "master":
                    self._master_weights[id(p)] = Tensor(arr)
                else:
                    self._acc(suffix, p, init=arr)
                break

    set_dict = set_state_dict

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..nn.layer.layers import in_dynamic_mode
        if not in_dynamic_mode():
            # static build: register backward+update for each Executor.run
            # (the reference appends backward + optimizer ops to the program)
            from ..static import default_main_program
            default_main_program()._add_minimize(self, loss)
            return None, None
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None


def name_of(p):
    return p.name


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)

    def _update_param(self, p, g, lr):
        m = self._master(p)
        g32 = self._decayed(p, g._data.astype(jnp.float32), m._data)
        self._apply(p, m._data - lr * g32)


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _update_param(self, p, g, lr):
        m = self._master(p)
        g32 = self._decayed(p, g._data.astype(jnp.float32), m._data)
        vel = self._acc("velocity", p)
        v_new = self._momentum * vel._data + g32
        vel._data = v_new
        if self._nesterov:
            upd = g32 + self._momentum * v_new
        else:
            upd = v_new
        self._apply(p, m._data - lr * upd)


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _adam_update(self, p, g, lr, decoupled_wd=0.0):
        mw = self._master(p)
        g32 = g._data.astype(jnp.float32)
        if decoupled_wd == 0.0:
            g32 = self._decayed(p, g32, mw._data)
        m = self._acc("moment1", p)
        v = self._acc("moment2", p)
        b1p = self._acc("beta1_pow", p, init=jnp.ones((), jnp.float32))
        b2p = self._acc("beta2_pow", p, init=jnp.ones((), jnp.float32))
        b1p._data = b1p._data * self._beta1
        b2p._data = b2p._data * self._beta2
        m._data = self._beta1 * m._data + (1 - self._beta1) * g32
        v._data = self._beta2 * v._data + (1 - self._beta2) * g32 * g32
        mhat = m._data / (1 - b1p._data)
        vhat = v._data / (1 - b2p._data)
        new = mw._data - lr * (mhat / (jnp.sqrt(vhat) + self._epsilon)
                               + decoupled_wd * mw._data)
        self._apply(p, new)

    def _update_param(self, p, g, lr):
        self._adam_update(p, g, lr, 0.0)


class AdamW(Adam):
    """Decoupled weight decay Adam — the north-star fused adamw kernel.

    Parity: python/paddle/optimizer/adamw.py + AdamwDenseKernel. The
    apply_decay_param_fun predicate matches the reference (skip decay for
    bias/LayerNorm via user fn).
    """

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision)
        self._wd_coeff = weight_decay if isinstance(weight_decay, float) else \
            getattr(weight_decay, "_coeff", 0.01)
        self._apply_decay_fn = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _update_param(self, p, g, lr):
        wd = self._wd_coeff
        if self._apply_decay_fn is not None and not self._apply_decay_fn(p.name):
            wd = 0.0
        if self._lr_ratio is not None:
            lr = lr * self._lr_ratio(p)
        self._adam_update(p, g, lr, wd)


class Adafactor(Optimizer):
    """Factored-second-moment Adam (Shazeer & Stern 2018).

    The fix the 1B single-chip OOM analysis drives (LLAMA1B_cpu_mesh.json
    / tools/llama_1b.py): AdamW's two full fp32 moments cost 10 GB at
    1.26B params, pushing total state past the 16 GB v5e HBM; Adafactor
    keeps row+col statistics instead (KBs per matrix), so state =
    params (+ optional fp32 master) + ~0. Matrices (and the last two
    axes of higher-rank params, e.g. stacked experts) are factored;
    vectors keep a full second moment (negligible).

    Follows the paper's recommended config: beta2_t = 1 - t^-decay_rate,
    update clipped to clip_threshold by RMS, optional parameter-scaled
    lr (scale_parameter). relative_step is intentionally NOT implemented
    — lr comes from this framework's scheduler machinery like every
    other optimizer here."""

    def __init__(self, learning_rate=1e-3, beta1=None, decay_rate=0.8,
                 epsilon1=1e-30, epsilon2=1e-3, clip_threshold=1.0,
                 scale_parameter=True, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._decay_rate = decay_rate
        self._eps1 = epsilon1
        self._eps2 = epsilon2
        self._clip_threshold = clip_threshold
        self._scale_parameter = scale_parameter

    def _update_param(self, p, g, lr):
        mw = self._master(p)
        g32 = self._decayed(p, g._data.astype(jnp.float32), mw._data)
        step = self._acc("step", p, init=jnp.zeros((), jnp.float32))
        step._data = step._data + 1.0
        t = step._data
        beta2_t = 1.0 - t ** (-self._decay_rate)
        g2 = g32 * g32 + self._eps1

        if p.ndim >= 2:
            # factor the last two axes; leading axes ride along (stacked
            # experts / conv kernels)
            vr = self._acc("vrow", p,
                           init=jnp.zeros(p.shape[:-1], jnp.float32))
            vc = self._acc("vcol", p, init=jnp.zeros(
                tuple(p.shape[:-2]) + (p.shape[-1],), jnp.float32))
            vr._data = beta2_t * vr._data + (1 - beta2_t) * jnp.mean(
                g2, axis=-1)
            vc._data = beta2_t * vc._data + (1 - beta2_t) * jnp.mean(
                g2, axis=-2)
            denom = jnp.mean(vr._data, axis=-1, keepdims=True)
            vhat = (vr._data / jnp.maximum(denom, self._eps1))[..., None] \
                * vc._data[..., None, :]
        else:
            v = self._acc("moment2", p)
            v._data = beta2_t * v._data + (1 - beta2_t) * g2
            vhat = v._data
        u = g32 / jnp.sqrt(jnp.maximum(vhat, self._eps1))
        rms_u = jnp.sqrt(jnp.mean(u * u) + self._eps1)
        u = u / jnp.maximum(1.0, rms_u / self._clip_threshold)
        if self._beta1 is not None:
            m = self._acc("moment1", p)
            m._data = self._beta1 * m._data + (1 - self._beta1) * u
            u = m._data
        alpha = lr
        if self._scale_parameter:
            rms_p = jnp.sqrt(jnp.mean(mw._data * mw._data))
            alpha = lr * jnp.maximum(rms_p, self._eps2)
        self._apply(p, mw._data - alpha * u)


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _update_param(self, p, g, lr):
        m = self._master(p)
        g32 = self._decayed(p, g._data.astype(jnp.float32), m._data)
        acc = self._acc("moment", p,
                        init=jnp.full_like(m._data, self._init_acc))
        acc._data = acc._data + g32 * g32
        self._apply(p, m._data - lr * g32 / (jnp.sqrt(acc._data) + self._epsilon))


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._epsilon = epsilon
        self._rho = rho

    def _update_param(self, p, g, lr):
        m = self._master(p)
        g32 = self._decayed(p, g._data.astype(jnp.float32), m._data)
        avg_sq = self._acc("_avg_squared_grad", p)
        avg_upd = self._acc("_avg_squared_update", p)
        avg_sq._data = self._rho * avg_sq._data + (1 - self._rho) * g32 * g32
        upd = (jnp.sqrt(avg_upd._data + self._epsilon) /
               jnp.sqrt(avg_sq._data + self._epsilon)) * g32
        avg_upd._data = self._rho * avg_upd._data + (1 - self._rho) * upd * upd
        self._apply(p, m._data - lr * upd)


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _update_param(self, p, g, lr):
        mw = self._master(p)
        g32 = self._decayed(p, g._data.astype(jnp.float32), mw._data)
        ms = self._acc("mean_square", p)
        mom = self._acc("momentum", p)
        ms._data = self._rho * ms._data + (1 - self._rho) * g32 * g32
        denom = ms._data
        if self._centered:
            mg = self._acc("mean_grad", p)
            mg._data = self._rho * mg._data + (1 - self._rho) * g32
            denom = denom - mg._data * mg._data
        mom._data = self._momentum * mom._data + lr * g32 / jnp.sqrt(
            denom + self._epsilon)
        self._apply(p, mw._data - mom._data)


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name,
                         multi_precision)
        self._wd = lamb_weight_decay
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _update_param(self, p, g, lr):
        mw = self._master(p)
        g32 = g._data.astype(jnp.float32)
        m = self._acc("moment1", p)
        v = self._acc("moment2", p)
        b1p = self._acc("beta1_pow", p, init=jnp.ones((), jnp.float32))
        b2p = self._acc("beta2_pow", p, init=jnp.ones((), jnp.float32))
        b1p._data = b1p._data * self._beta1
        b2p._data = b2p._data * self._beta2
        m._data = self._beta1 * m._data + (1 - self._beta1) * g32
        v._data = self._beta2 * v._data + (1 - self._beta2) * g32 * g32
        mhat = m._data / (1 - b1p._data)
        vhat = v._data / (1 - b2p._data)
        wd = self._wd
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        r = mhat / (jnp.sqrt(vhat) + self._epsilon) + wd * mw._data
        w_norm = jnp.linalg.norm(mw._data)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        self._apply(p, mw._data - lr * trust * r)


# ---- round-2 breadth: Adamax, NAdam, RAdam, ASGD, Rprop -------------------
# Parity: python/paddle/optimizer/{adamax,nadam,radam,asgd,rprop}.py.

class Adamax(Optimizer):
    """Adam with infinity-norm second moment (no bias correction on v)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _update_param(self, p, g, lr):
        mw = self._master(p)
        g32 = self._decayed(p, g._data.astype(jnp.float32), mw._data)
        m = self._acc("moment", p)
        u = self._acc("inf_norm", p)
        b1p = self._acc("beta1_pow", p, init=jnp.ones((), jnp.float32))
        b1p._data = b1p._data * self._beta1
        m._data = self._beta1 * m._data + (1 - self._beta1) * g32
        u._data = jnp.maximum(self._beta2 * u._data, jnp.abs(g32))
        new = mw._data - (lr / (1 - b1p._data)) * m._data / (
            u._data + self._epsilon)
        self._apply(p, new)


class NAdam(Optimizer):
    """Adam with Nesterov momentum (reference nadam.py formulas)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, momentum_decay=0.004, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._psi = momentum_decay

    def _update_param(self, p, g, lr):
        mw = self._master(p)
        g32 = self._decayed(p, g._data.astype(jnp.float32), mw._data)
        m = self._acc("moment1", p)
        v = self._acc("moment2", p)
        step = self._acc("step", p, init=jnp.zeros((), jnp.float32))
        mu_prod = self._acc("mu_prod", p, init=jnp.ones((), jnp.float32))
        b2p = self._acc("beta2_pow", p, init=jnp.ones((), jnp.float32))
        step._data = step._data + 1.0
        t = step._data
        mu_t = self._beta1 * (1 - 0.5 * 0.96 ** (t * self._psi))
        mu_next = self._beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * self._psi))
        mu_prod_t = mu_prod._data * mu_t
        mu_prod._data = mu_prod_t
        b2p._data = b2p._data * self._beta2
        m._data = self._beta1 * m._data + (1 - self._beta1) * g32
        v._data = self._beta2 * v._data + (1 - self._beta2) * g32 * g32
        mhat = (mu_next * m._data / (1 - mu_prod_t * mu_next)
                + (1 - mu_t) * g32 / (1 - mu_prod_t))
        vhat = v._data / (1 - b2p._data)
        self._apply(p, mw._data - lr * mhat
                    / (jnp.sqrt(vhat) + self._epsilon))


class RAdam(Optimizer):
    """Rectified Adam: variance-rectification term gates between SGDm and
    Adam (reference radam.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _update_param(self, p, g, lr):
        mw = self._master(p)
        g32 = self._decayed(p, g._data.astype(jnp.float32), mw._data)
        m = self._acc("moment1", p)
        v = self._acc("moment2", p)
        step = self._acc("step", p, init=jnp.zeros((), jnp.float32))
        step._data = step._data + 1.0
        t = step._data
        b1p = self._beta1 ** t
        b2p = self._beta2 ** t
        m._data = self._beta1 * m._data + (1 - self._beta1) * g32
        v._data = self._beta2 * v._data + (1 - self._beta2) * g32 * g32
        mhat = m._data / (1 - b1p)
        rho_inf = 2.0 / (1 - self._beta2) - 1.0
        rho_t = rho_inf - 2.0 * t * b2p / (1 - b2p)
        # rectified branch when rho_t > 5 (reference threshold)
        r = jnp.sqrt(jnp.maximum(
            (rho_t - 4) * (rho_t - 2) * rho_inf
            / jnp.maximum((rho_inf - 4) * (rho_inf - 2) * rho_t, 1e-12),
            0.0))
        vhat = jnp.sqrt(v._data / (1 - b2p)) + self._epsilon
        adam_step = r * mhat / vhat
        sgd_step = mhat
        self._apply(p, mw._data - lr * jnp.where(rho_t > 5.0, adam_step,
                                                 sgd_step))


class ASGD(Optimizer):
    """Averaged SGD (reference asgd.py): steps use the MEAN of the last
    `batch_num` gradients via the d/ys recursion (d ← d − ys[i] + g;
    ys[i] ← g), plus a running parameter average for inference."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._batch_num = int(batch_num)

    def _update_param(self, p, g, lr):
        mw = self._master(p)
        g32 = self._decayed(p, g._data.astype(jnp.float32), mw._data)
        n = self._batch_num
        step = self._acc("step", p, init=lambda: jnp.zeros((), jnp.float32))
        avg = self._acc("averaged", p, init=lambda: mw._data)
        d = self._acc("d", p)
        ys = self._acc("ys", p, init=lambda: jnp.zeros(
            (n, *mw._data.shape), jnp.float32))
        t = step._data
        idx = (t % n).astype(jnp.int32)
        d._data = d._data - ys._data[idx] + g32
        ys._data = ys._data.at[idx].set(g32)
        step._data = t + 1.0
        seen = jnp.minimum(t + 1.0, float(n))
        new = mw._data - lr * d._data / seen
        avg._data = avg._data + (new - avg._data) / (t + 1.0)
        self._apply(p, new)

    def averaged_value(self, p):
        return self._acc("averaged", p)


class Rprop(Optimizer):
    """Resilient backprop: per-weight step sizes adapted by grad-sign
    agreement (reference rprop.py)."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         name, multi_precision)
        self._eta_minus, self._eta_plus = etas
        self._lr_min, self._lr_max = learning_rate_range

    def _update_param(self, p, g, lr):
        mw = self._master(p)
        g32 = g._data.astype(jnp.float32)
        prev = self._acc("prev_grad", p)
        # lr (from get_lr) honors schedulers; init only runs on first use
        steps = self._acc("step_size", p,
                          init=lambda: jnp.full_like(mw._data, lr))
        sign = g32 * prev._data
        grow = sign > 0
        shrink = sign < 0
        steps._data = jnp.clip(
            jnp.where(grow, steps._data * self._eta_plus,
                      jnp.where(shrink, steps._data * self._eta_minus,
                                steps._data)),
            self._lr_min, self._lr_max)
        # on sign flip: zero the grad (classic Rprop- variant)
        eff_g = jnp.where(shrink, 0.0, g32)
        prev._data = eff_g
        self._apply(p, mw._data - jnp.sign(eff_g) * steps._data)
