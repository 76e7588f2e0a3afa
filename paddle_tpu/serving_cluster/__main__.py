"""python -m paddle_tpu.serving_cluster — a self-contained demo
cluster: N replicas (each its own ServingEngine + prefix cache over a
shared toy model) behind the gateway, ready for curl.

    JAX_PLATFORMS=cpu python -m paddle_tpu.serving_cluster \
        --replicas 2 --port 8100
    curl -s localhost:8100/v1/models
    curl -s localhost:8100/v1/completions -d \
        '{"prompt": [5, 9, 2, 41], "max_tokens": 8}'
    curl -sN localhost:8100/v1/completions -d \
        '{"prompt": [5, 9, 2, 41], "max_tokens": 8, "stream": true}'
    curl -s localhost:8100/metrics | head

``--workers N`` promotes the replicas OUT OF PROCESS: the gateway
process becomes a supervisor that spawns N worker processes as a gang
(workerlog capture, SIGTERM->grace->SIGKILL teardown — the same
discipline as distributed.launch), rendezvouses them over
``distributed.rpc``, and fronts each with an ``RpcReplica``. Each
worker builds its own engine and calls ``serve_engine`` — the
production recipe (one engine per accelerator process) instead of the
manual ``init_rpc`` glue. A dead worker tears the whole demo down
with a failure report naming the rank and its log tail.

``--mesh-mp M`` makes every engine tensor-parallel over an M-way mesh
(``parallel.init_serving_mesh``): the paged KV pool shards by head AND
the stacked qkv/proj/FFN weights (plus the LM head) shard over 'mp',
so each device holds ~1/M of both the pool and the weight bytes
(``PADDLE_SERVING_MESH_WEIGHTS=0`` opts the weight half out). Workers
inherit the degree via ``PADDLE_SERVING_MESH_MP``; the bring-up
validates the model's head/FFN axes against M up front. On CPU hosts
the mesh devices are forced via XLA_FLAGS automatically.

Flags default from the env contract (``PADDLE_GATEWAY_PORT``,
``PADDLE_GATEWAY_REPLICAS``, ``PADDLE_ROUTER_POLICY``,
``PADDLE_SERVING_MESH_MP``). This is the demo/e2e harness; a real
deployment builds its own engines (one per accelerator) and passes
them to ``LocalReplica``/``serve_engine``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


# the demo cluster's shared toy-model dims — module-level so the mesh
# bring-up can validate the tensor-parallel layout (H % mp, FF % mp)
# BEFORE any engine build
MODEL_DIMS = {"E": 64, "H": 4, "FF": 128, "L": 2, "V": 256}


def _build_engine(seed, slots, smax, prefix_blocks, cap, role="mixed"):
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import FusedMultiTransformer
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.nn.layer.common import Embedding, Linear

    E, H, FF, L, V = (MODEL_DIMS[k] for k in ("E", "H", "FF", "L", "V"))
    paddle.seed(seed)
    embed = Embedding(V, E)
    fmt = FusedMultiTransformer(E, H, FF, num_layers=L,
                                normalize_before=True)
    head = Linear(E, V, bias_attr=False)
    fmt.eval()
    kw = dict(num_slots=slots, max_seq_len=smax, prefill_cap=cap,
              prefix_cache_blocks=prefix_blocks, role=role)
    if role == "prefill":
        # prompt-crunching shape: few slots, one wide flat token
        # budget — the whole batch is prefill chunks, decode never
        # competes for the budget on this engine
        kw.update(num_slots=max(2, slots // 2), flat_budget=True,
                  token_budget=4 * cap, decode_chunk=1)
    elif role == "decode":
        # token-pump shape: deep slot count, small per-step budget —
        # many resident sessions, short steps, low inter-token jitter
        kw.update(num_slots=2 * slots, token_budget=2 * slots)
    return ServingEngine(fmt, embed, head, **kw)


def _parse_roles(spec):
    """'prefill:1,decode:2' -> ["prefill", "decode", "decode"]. The
    pool must be able to both place prompts and decode them: at least
    one prefill-capable AND one decode-capable entry."""
    roles = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, cnt = part.partition(":")
        name = name.strip()
        if name not in ("prefill", "decode", "mixed"):
            raise SystemExit(
                f"--roles: unknown role {name!r} (want prefill, "
                "decode, or mixed)")
        try:
            n = int(cnt)
        except ValueError:
            raise SystemExit(f"--roles: bad count in {part!r}")
        if n < 1:
            raise SystemExit(f"--roles: count must be >= 1 in {part!r}")
        roles.extend([name] * n)
    if not any(r in ("prefill", "mixed") for r in roles):
        raise SystemExit("--roles: no prefill-capable replica — "
                         "prompts would have nowhere to land")
    if not any(r in ("decode", "mixed") for r in roles):
        raise SystemExit("--roles: no decode-capable replica — "
                         "prefilled sessions would have nowhere to go")
    return roles


def _worker_main(args):
    """Worker-process entry (the supervisor re-execs this module with
    --worker-rank): join the rpc rendezvous FIRST (registration is
    cheap — the supervisor's 60s window must not pay for engine
    compiles), then build the engine and serve it."""
    from paddle_tpu.distributed import rpc
    from paddle_tpu.parallel import init_serving_mesh

    from .replica import serve_engine

    rank = args.worker_rank
    world = args.workers + 1
    last = None
    for _ in range(200):      # the supervisor's store server races us up
        try:
            rpc.init_rpc(f"cluster_worker{rank}", rank=rank,
                         world_size=world)
            break
        except (OSError, ConnectionError) as e:
            last = e
            time.sleep(0.1)
    else:
        raise RuntimeError(
            f"worker {rank}: rpc rendezvous never came up: {last!r}")
    # PADDLE_SERVING_MESH_MP; unset = no mesh. The model dims validate
    # the full tensor-parallel layout (KV heads + FFN columns) at
    # bring-up — a role worker must fail HERE, not mid-serve
    init_serving_mesh(num_heads=MODEL_DIMS["H"],
                      ffn_dim=MODEL_DIMS["FF"])
    eng = _build_engine(0, args.slots, args.max_seq_len,
                        args.prefix_blocks, args.prefill_cap,
                        role=args.role)
    serve_engine(eng, name=f"replica{rank}", threaded=True)
    print(f"serving_cluster: worker {rank} serving", flush=True)
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        pass
    finally:
        rpc.shutdown()
    return 0


def _spawn_workers(args, master, role_list=None):
    """Spawn the worker gang with workerlog capture; a mid-loop spawn
    failure reaps the already-started ranks (launch discipline).
    ``role_list`` (from --roles) assigns rank r its role by position —
    the worker builds its engine with the matching per-role shape."""
    import subprocess

    from paddle_tpu.distributed.launch.__main__ import _reap_gang

    os.makedirs(args.log_dir, exist_ok=True)
    procs, logs = [], []
    try:
        for rank in range(1, args.workers + 1):
            env = dict(os.environ)
            env["PADDLE_MASTER"] = master
            if args.mesh_mp > 1:
                env["PADDLE_SERVING_MESH_MP"] = str(args.mesh_mp)
            role = (role_list[rank - 1] if role_list is not None
                    else "mixed")
            logf = open(os.path.join(
                args.log_dir, f"workerlog.serving.{rank}"), "a")
            logs.append(logf)
            p = subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.serving_cluster",
                 "--worker-rank", str(rank),
                 "--workers", str(args.workers),
                 "--slots", str(args.slots),
                 "--max-seq-len", str(args.max_seq_len),
                 "--prefill-cap", str(args.prefill_cap),
                 "--prefix-blocks", str(args.prefix_blocks),
                 "--role", role],
                env=env, stdout=logf, stderr=subprocess.STDOUT)
            p._pd_rank = rank
            procs.append(p)
    except Exception:
        _reap_gang(procs, 5.0)
        for f in logs:
            f.close()
        raise
    return procs, logs


def _wait_ready(replicas, timeout_s=120.0):
    """Block until every worker has installed its engine: registration
    happens before the (slow) engine build, so the first snapshot may
    find no served engine yet — that RuntimeError is 'not ready', any
    transport error is a dead worker."""
    from .replica import ReplicaError

    deadline = time.time() + timeout_s
    for rep in replicas:
        while True:
            try:
                rep.snapshot()
                break
            except ReplicaError:
                raise
            except Exception:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"worker {rep.name!r} never became ready")
                time.sleep(0.25)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serving_cluster",
        description="demo cluster: N replicas behind the gateway")
    ap.add_argument("--replicas", type=int, default=int(os.environ.get(
        "PADDLE_GATEWAY_REPLICAS", "2")))
    ap.add_argument("--port", type=int, default=int(os.environ.get(
        "PADDLE_GATEWAY_PORT", "8100")))
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--prefill-cap", type=int, default=64)
    ap.add_argument("--prefix-blocks", type=int, default=64)
    ap.add_argument("--policy", default=None,
                    help="router policy (default: PADDLE_ROUTER_POLICY "
                         "or prefix_affinity)")
    ap.add_argument("--workers", type=int, default=0,
                    help="spawn N out-of-process rpc workers instead of "
                         "in-process replicas (supervised gang)")
    ap.add_argument("--mesh-mp", type=int, default=int(os.environ.get(
        "PADDLE_SERVING_MESH_MP", "0") or 0),
        help="tensor-parallel engines over an mp-way mesh: the paged "
             "KV pool shards by head and the qkv/proj/FFN weight "
             "stacks by head/column (0/1 = no mesh)")
    ap.add_argument("--log-dir", default="log",
                    help="worker gang log directory (workerlog.serving.N)")
    ap.add_argument("--roles", default=os.environ.get(
        "PADDLE_GATEWAY_ROLES", ""),
        help="disaggregated pool spec 'prefill:1,decode:2' — builds "
             "role-specialized replicas (prefill: flat-budget wide; "
             "decode: deep slots) instead of --replicas mixed ones; "
             "with --workers the spec also sets the worker count")
    ap.add_argument("--worker-rank", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--role", default="mixed",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    role_list = _parse_roles(args.roles) if args.roles else None

    # the mesh needs devices before the first jax import (CPU hosts:
    # forced host devices)
    if args.mesh_mp > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{max(8, args.mesh_mp)}").strip()

    if args.worker_rank:
        return _worker_main(args)

    from .gateway import Gateway
    from .router import Router

    procs, logs = [], []
    if args.workers > 0:
        from paddle_tpu.distributed import rpc
        from paddle_tpu.distributed.launch.__main__ import (_free_port,
                                                            _reap_gang,
                                                            _tail)

        from .replica import RpcReplica

        if role_list is not None:
            args.workers = len(role_list)
        master = f"127.0.0.1:{_free_port()}"
        procs, logs = _spawn_workers(args, master, role_list)
        # rank 0 hosts the store; init blocks until the gang registers
        rpc.init_rpc("cluster_gateway", rank=0,
                     world_size=args.workers + 1, master_endpoint=master)
        replicas = [RpcReplica(f"cluster_worker{r}")
                    for r in range(1, args.workers + 1)]
        _wait_ready(replicas)
        n_label = (f"{args.workers} worker processes ({args.roles})"
                   if role_list else f"{args.workers} worker processes")
    else:
        from paddle_tpu.parallel import init_serving_mesh

        from .replica import LocalReplica
        if args.mesh_mp > 1:
            init_serving_mesh(args.mesh_mp,
                              num_heads=MODEL_DIMS["H"],
                              ffn_dim=MODEL_DIMS["FF"])
        # every replica serves the SAME weights (seed-shared toy model)
        # so routing is invisible to outputs — the production contract
        roles = role_list or ["mixed"] * args.replicas
        replicas = [
            LocalReplica(f"{role}{i}" if role_list else f"replica{i}",
                         _build_engine(0, args.slots, args.max_seq_len,
                                       args.prefix_blocks,
                                       args.prefill_cap, role=role))
            for i, role in enumerate(roles)]
        n_label = (f"{len(roles)} replicas ({args.roles})"
                   if role_list else f"{args.replicas} replicas")

    router = Router(replicas, policy=args.policy)
    gw = Gateway(router, port=args.port).start_background()
    mesh_note = (f", mesh mp={args.mesh_mp}" if args.mesh_mp > 1 else "")
    print(f"serving_cluster: {n_label} on "
          f"http://127.0.0.1:{gw.port} (policy {router.policy}"
          f"{mesh_note}) — Ctrl-C to stop", flush=True)
    rc = 0
    try:
        while True:
            time.sleep(1)
            # gang supervision: the first dead worker tears down the
            # demo with a report naming the rank and its log tail
            dead = [p for p in procs if p.poll() is not None]
            if dead:
                p = dead[0]
                path = os.path.join(args.log_dir,
                                    f"workerlog.serving.{p._pd_rank}")
                print(f"serving_cluster: worker {p._pd_rank} died "
                      f"(exit {p.poll()}):\n{_tail(path)}",
                      file=sys.stderr, flush=True)
                rc = 1
                break
    except KeyboardInterrupt:
        pass
    finally:
        gw.stop()
        for r in replicas:
            try:
                r.close()
            except Exception:
                pass
        if procs:
            _reap_gang(procs, 5.0)
            for f in logs:
                f.close()
            rpc.shutdown()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
