"""Distributed bootstrap. Parity: python/paddle/distributed/parallel.py ::
init_parallel_env + ParallelEnv.

Reference flow: parse PADDLE_TRAINER_ID/PADDLE_TRAINER_ENDPOINTS env, TCPStore
rendezvous, create default ProcessGroupNCCL. TPU-native flow: the JAX
coordination service replaces TCPStore (jax.distributed.initialize), and the
"default process group" is the global device mesh — collectives are XLA ops
over ICI/DCN, not NCCL rings.

Rank semantics on a single-controller SPMD runtime:
  * host-side code (data loading, logging, checkpoint IO) sees
    process-level rank/world (one process per host);
  * per-chip rank differences live INSIDE compiled programs (mesh
    coordinates), not in Python control flow.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np

from .resilience.flight_recorder import instrumented as _fr_instrumented

__all__ = ["init_parallel_env", "get_rank", "get_world_size", "ParallelEnv",
           "all_reduce_gradients", "is_initialized_env"]

_state = {"initialized": False, "rank": 0, "world_size": 1, "mesh": None}


def _maybe_start_watchdog(rank: int, world: int):
    """Start the heartbeat watchdog (resilience/watchdog.py) over the same
    TCPStore daemon _store_barrier runs one port above the coordinator.
    Multi-process only; PADDLE_WATCHDOG_TIMEOUT_S=0 disables; best-effort
    when the native runtime is unavailable."""
    if world <= 1:
        return
    if float(os.environ.get("PADDLE_WATCHDOG_TIMEOUT_S", "300")) <= 0:
        return
    coord = os.environ.get("PADDLE_MASTER",
                           os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if not coord:
        return
    try:
        from ..core.native import TCPStore, load_native
        if load_native() is None:
            return
    except Exception:
        return
    host, port = coord.rsplit(":", 1)
    store_port = int(port) + 1
    connect_t = float(os.environ.get("PADDLE_STORE_CONNECT_TIMEOUT", "15"))

    def factory(timeout_s=None):
        return TCPStore(host, store_port,
                        timeout_s=connect_t if timeout_s is None
                        else timeout_s)

    try:  # one SHORT probe connection: no store daemon -> no watchdog
        # (full connect_t here would stall init when the rendezvous store
        # was skipped, e.g. its port was taken)
        TCPStore(host, store_port, timeout_s=min(connect_t, 2.0)).close()
    except Exception:
        import logging
        logging.warning("paddle_tpu: heartbeat watchdog disabled (store "
                        "%s:%d unreachable)", host, store_port)
        return
    from .resilience import start_watchdog
    start_watchdog(factory, rank, world)


def _maybe_jax_distributed_init():
    """Multi-host init from PADDLE_* or JAX_* env (TCPStore-equivalent)."""
    n = int(os.environ.get("PADDLE_TRAINERS_NUM",
                           os.environ.get("JAX_NUM_PROCESSES", "1")))
    if n <= 1:
        return
    # must NOT call jax.process_count() here: it initializes the XLA
    # backend, after which jax.distributed.initialize refuses to run —
    # probe the distributed client state instead
    try:
        from jax._src import distributed as _jd
        if getattr(_jd.global_state, "client", None) is not None:
            return
    except Exception:
        pass
    coord = os.environ.get("PADDLE_MASTER",
                           os.environ.get("JAX_COORDINATOR_ADDRESS"))
    pid = int(os.environ.get("PADDLE_TRAINER_ID",
                             os.environ.get("JAX_PROCESS_ID", "0")))
    try:
        # jax < 0.5 leaves CPU collectives on the XLA default, which
        # raises "Multiprocess computations aren't implemented on the
        # CPU backend" at the first cross-process op; newer jax defaults
        # to gloo and drops the flag (hence best-effort). Must be set
        # BEFORE the backend client is created — i.e. right here, ahead
        # of jax.distributed.initialize.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass
    if coord:
        _store_barrier(coord, n, pid)
        try:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=n, process_id=pid)
        except RuntimeError:
            # already initialized (user called it, or the private-state
            # probe above failed on a newer jax) — proceed with the
            # existing client
            if jax.process_count() != n:
                raise


def _store_barrier(coord: str, world: int, rank: int):
    """Pre-init rendezvous over the native TCPStore (csrc/runtime.cc —
    parity: paddle/fluid/distributed/store/tcp_store.cc): rank 0 runs the
    master daemon one port above the coordinator port, every rank registers
    and waits until all are present, so jax.distributed.initialize never
    races a late-starting coordinator. Best-effort: skipped when the native
    runtime is unavailable."""
    try:
        from ..core.native import TCPStore, TCPStoreServer
    except Exception:
        return
    import logging
    try:
        host, port = coord.rsplit(":", 1)
        store_port = int(port) + 1
        if rank == 0:
            try:
                srv = TCPStoreServer(store_port)
                _state["_store_server"] = srv   # keep alive for the job
            except OSError as e:
                logging.warning(
                    "paddle_tpu: TCPStore barrier master failed to bind "
                    "port %d (%s); skipping pre-init rendezvous", store_port,
                    e)
                return
        # bounded connect: if the master never comes up, fall through to
        # jax.distributed.initialize (which has its own retry) instead of
        # stalling the job for the full store timeout
        c = TCPStore(host, store_port,
                     timeout_s=float(os.environ.get(
                         "PADDLE_STORE_CONNECT_TIMEOUT", "15")))
        c.add("init/count", 1)
        if rank == 0:
            # BOUNDED wait: a peer whose store connect failed skips the
            # rendezvous entirely (best-effort contract), so an open
            # wait here would deadlock the whole job — rank 0 stuck in
            # this loop never reaches jax.distributed.initialize, and
            # every other rank then blocks inside it forever. On
            # timeout, release any ranks that DID register and fall
            # through to jax.distributed.initialize, which is the real
            # (coordinator-side) rendezvous anyway.
            import time
            deadline = time.time() + float(os.environ.get(
                "PADDLE_STORE_CONNECT_TIMEOUT", "15"))
            while c.get("init/count") is None or \
                    int.from_bytes(c.get("init/count")[:8], "little",
                                   signed=True) < world:
                if time.time() > deadline:
                    logging.warning(
                        "paddle_tpu: TCPStore pre-init rendezvous timed "
                        "out with %s/%d ranks registered; proceeding",
                        c.get("init/count") and int.from_bytes(
                            c.get("init/count")[:8], "little",
                            signed=True), world)
                    break
                time.sleep(0.05)
            c.set("init/ready", b"1")
        c.wait("init/ready", timeout_s=float(os.environ.get(
            "PADDLE_STORE_TIMEOUT", "300")))
        c.close()
    except Exception as e:
        logging.warning("paddle_tpu: TCPStore pre-init rendezvous skipped "
                        "(%s)", e)


def init_parallel_env():
    if _state["initialized"]:
        return ParallelEnv()
    _maybe_jax_distributed_init()
    _state["rank"] = jax.process_index()
    _state["world_size"] = jax.process_count()
    _state["initialized"] = True
    from ..testing import fault
    fault.inject("init", rank=_state["rank"])
    # flight recorder: authoritative rank/world (default on at world>1),
    # and a SIGTERM dump hook so a rank the supervisor reaps leaves its
    # collective timeline behind for the cross-rank diagnosis
    from .resilience import flight_recorder
    flight_recorder.configure(rank=_state["rank"],
                              world=_state["world_size"])
    if _state["world_size"] > 1:
        flight_recorder.install_signal_dump()
    _maybe_start_watchdog(_state["rank"], _state["world_size"])
    from .communication.group import _ensure_default_group
    _ensure_default_group()
    return ParallelEnv()


def is_initialized_env() -> bool:
    return _state["initialized"]


def get_rank(group=None) -> int:
    if group is not None:
        from .communication.group import Group
        if isinstance(group, Group):
            return group.get_group_rank(_state["rank"])
    return _state["rank"] if _state["initialized"] else jax.process_index()


def get_world_size(group=None) -> int:
    if group is not None:
        from .communication.group import Group
        if isinstance(group, Group):
            return group.nranks
    return _state["world_size"] if _state["initialized"] else jax.process_count()


class ParallelEnv:
    """Parity: python/paddle/distributed/parallel.py :: ParallelEnv."""

    @property
    def rank(self) -> int:
        return get_rank()

    @property
    def world_size(self) -> int:
        return get_world_size()

    @property
    def local_rank(self) -> int:
        return int(os.environ.get("PADDLE_LOCAL_RANK", "0"))

    @property
    def nranks(self) -> int:
        return get_world_size()

    @property
    def dev_id(self) -> int:
        return self.local_rank

    @property
    def device_type(self) -> str:
        return jax.devices()[0].platform

    @property
    def trainer_endpoints(self) -> list:
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else []

    @property
    def current_endpoint(self) -> str:
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "")


@_fr_instrumented("all_reduce_gradients")
def all_reduce_gradients(params, group=None):
    """DataParallel grad sync: mean-allreduce every .grad across dp ranks.

    Parity: EagerReducer's bucketed allreduce
    (paddle/fluid/distributed/collective/reducer.cc). Under XLA one fused
    program per step IS the bucket fusion; eagerly this is a no-op at
    world_size 1 and a psum at >1. Recorded as ONE logical collective in
    the flight recorder (the per-param all_reduce calls nest under it).
    """
    ws = get_world_size(group)
    if ws <= 1:
        return
    from .resilience import check_peer_failure
    check_peer_failure()   # fail fast instead of entering a doomed psum
    from .communication.all_reduce import all_reduce
    from ..tensor.tensor import no_grad
    with no_grad():
        for p in params:
            if p.grad is not None:
                all_reduce(p.grad, group=group)
                p.grad._data = p.grad._data / ws
