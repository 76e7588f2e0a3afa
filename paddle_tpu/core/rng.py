"""Global-seed facade over JAX PRNG keys.

Parity: ``paddle.seed`` + Fleet's ``RNGStatesTracker``
(python/paddle/distributed/fleet/layers/mpu/random.py). JAX's explicit keys
are stronger than the reference's global-seed model; this facade keeps the
Paddle-shaped API while every draw splits the global key.

TPU/jit-critical design: the key lives in a persistent Tensor, so
``paddle.jit.to_static`` functionalizes it like any parameter — random ops
inside a compiled train step thread the key through the program instead of
baking a trace-time constant (each call gets fresh randomness).
"""
from __future__ import annotations

import contextlib
import threading

import jax


def _impl() -> str:
    """PRNG implementation for all framework keys. Default "rbg": threefry
    split/fold semantics, but the bit draws lower to XLA RngBitGenerator —
    the TPU's hardware generator, far cheaper than threefry's ALU rounds and
    fusable into the consuming elementwise op (dropout masks cost ~0 extra
    HBM). Override with PADDLE_TPU_PRNG_IMPL=threefry2x32 for JAX-default
    bitstreams."""
    import os
    return os.environ.get("PADDLE_TPU_PRNG_IMPL", "rbg")


def _key(s: int):
    return jax.random.key(int(s), impl=_impl())


class _RngState(threading.local):
    def __init__(self):
        self.key_tensor = None
        self.seed_value = 0

    def ensure(self):
        # `_data is None` = the key was lazily created inside a to_static
        # trace that failed; the rollback (jit _execute) killed it. Rebuild
        # from the last seed so the retry reruns with live, tracked state.
        # A DELETED device array (device.chip.release_device_memory
        # hard-deletes all live arrays) rebuilds the same way.
        dead = (self.key_tensor is None or self.key_tensor._data is None)
        if not dead:
            is_del = getattr(self.key_tensor._data, "is_deleted", None)
            try:
                dead = bool(is_del()) if callable(is_del) else False
            except Exception:
                dead = False   # tracer mid-trace: live by definition
        if dead:
            from ..tensor.tensor import Tensor, register_persistent
            self.key_tensor = Tensor(_key(self.seed_value))
            self.key_tensor.name = "global_rng_key"
            self.key_tensor.persistable = True
            register_persistent(self.key_tensor)
        return self.key_tensor


_rng = _RngState()


def seed(s: int):
    t = _rng.ensure()
    t._data = _key(s)
    _rng.seed_value = int(s)
    return t


def get_seed() -> int:
    return _rng.seed_value


def next_key():
    """Fresh subkey; global key advances (threads through jit as state)."""
    t = _rng.ensure()
    k1, k2 = jax.random.split(t._data)
    t._data = k1
    return k2


def next_threefry_key():
    """Fresh subkey guaranteed to be threefry — for the few jax.random
    samplers (poisson) not implemented for the rbg impl. Derived
    deterministically from the global stream regardless of its impl."""
    k = next_key()
    if str(jax.random.key_impl(k)) == "threefry2x32":
        return k
    bits = jax.random.bits(k, (2,), "uint32")
    return jax.random.wrap_key_data(bits, impl="threefry2x32")


def get_rng_state():
    return _rng.ensure()._data


def get_rng_tensor():
    """The persistent tensor that holds the global key."""
    return _rng.ensure()


def set_rng_state(state):
    t = _rng.ensure()
    if isinstance(state, int):
        t._data = _key(state)
    else:
        t._data = state


class RNGStatesTracker:
    """Named RNG streams (model-parallel dropout determinism).

    Parity: fleet/layers/mpu/random.py :: RNGStatesTracker. add() registers a
    named stream with its own seed; rng_state(name) switches draws to it.
    """

    def __init__(self):
        self.states_: dict[str, object] = {}
        self.seeds_: set[int] = set()

    def reset(self):
        self.states_.clear()
        self.seeds_.clear()

    def add(self, name: str, seed_: int):
        if seed_ in self.seeds_:
            raise ValueError(f"seed {seed_} already exists")
        if name in self.states_:
            raise ValueError(f"state {name} already exists")
        self.seeds_.add(seed_)
        self.states_[name] = _key(seed_)

    def get_states_tracker(self):
        return dict(self.states_)

    def set_states_tracker(self, states):
        self.states_ = dict(states)

    @contextlib.contextmanager
    def rng_state(self, name: str = "model_parallel_rng"):
        if name not in self.states_:
            raise ValueError(f"state {name} does not exist")
        t = _rng.ensure()
        orig = t._data
        t._data = self.states_[name]
        try:
            yield
        finally:
            self.states_[name] = t._data
            t._data = orig


_RNG_TRACKER = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _RNG_TRACKER


MODEL_PARALLEL_RNG = "model_parallel_rng"


def model_parallel_random_seed(seed_: int = 100):
    """TP dropout determinism: global stream shared, mp stream offset by the
    tensor-parallel rank (reference: mpu/random.py :: model_parallel_random_seed)."""
    import random as _pyrandom
    global_seed = seed_
    local_seed = seed_ + 1024
    try:
        from ..distributed.fleet.base.topology import _HYBRID_GROUP
        if _HYBRID_GROUP[0] is not None:
            local_seed = seed_ + 1024 + _HYBRID_GROUP[0].get_model_parallel_rank()
    except Exception:
        pass
    _RNG_TRACKER.reset()
    seed(global_seed)
    _pyrandom.seed(global_seed)
    _RNG_TRACKER.add(MODEL_PARALLEL_RNG, local_seed)
