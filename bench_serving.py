"""Serving micro-bench: continuous batching vs static batching on the
SAME compiled decode step (ServingEngine over the stacked KV ring cache).

Synthetic mixed-length workload with Poisson arrivals on a VIRTUAL clock
(idle waits are skipped, compute time is real), fixed seed. Two drivers:

  * continuous — requests are admitted the moment a slot frees (the
    engine's native behavior);
  * static     — gang scheduling: a batch of `num_slots` requests is
    submitted only when the engine is fully idle and every member has
    arrived, so finished rows idle until the slowest row ends (the
    classic static-batch throughput killer).

Both run the same engine class, same compiled-step shape, same workload.
Every timed mode runs on a TPU or exits non-zero (no CPU fallback, no
toy-width model: paddle_tpu.device.chip.require_tpu); --mesh and
--mesh-weights are count drills that pin 8 virtual CPU devices on purpose
and write no rate or latency field. Prints ONE JSON line in the BENCH
record format; the full record also lands in BENCH_serving.json. The
records in the committed BENCH_serving.json predate this rule (CPU,
hidden 64-256): only their counts and byte totals mean anything.
ROADMAP.md S1/D5 replace this script with a cell-driven benchmark.

Env knobs: BENCH_SLOTS, BENCH_SERVE_REQUESTS, BENCH_SERVE_WARMUP,
BENCH_SERVE_CHUNK, BENCH_SERVE_SEED, BENCH_SERVE_LOAD (offered load vs
measured capacity, default 1.5 — backlog forms, continuous batching's
favorable regime and the honest serving scenario).

--shared-prompts runs the PREFIX-CACHE workload instead: N prompt
templates (shared system prompts) x Poisson arrivals, the same engine
with the prefix cache ON vs OFF at equal compiled shape — reporting
prefix hit-rate, prefill tokens computed vs admitted, TTFT p50/p99,
tokens/s speedup, and the zero-retrace contract. Its knobs:
BENCH_PREFIX_TEMPLATES (4), BENCH_PREFIX_TLEN (template tokens),
BENCH_PREFIX_CAP (prefill_cap == prefix block size),
BENCH_PREFIX_BLOCKS (pool budget).

--spec runs the SPECULATIVE-DECODING workload: repetitive-output
(summarize/echo-style) prompts under Poisson arrivals, the same engine
with the n-gram drafter + compiled K+1 verify step ON vs OFF at equal
compiled shape and the SAME arrivals — reporting acceptance rate,
tokens/step, tokens/s speedup and the zero-retrace contract. Its knob:
BENCH_SPEC_K (draft length, default 4).

--paged runs the PAGED-KV-CACHE capacity A/B: the paged engine (ONE
block pool + per-slot block tables, pool sized to EXACTLY the dense
engine's KV bytes) vs the dense ring engine, same fixed-seed Poisson
workload and the SAME arrivals — reporting max concurrent slots (the
capacity win: slots are bounded by the pool, not B x Smax), tokens/s,
the zero-retrace contract, and an exact greedy paged-vs-dense token
parity check at equal shape. Its knobs: BENCH_PAGED_CAP (block tokens
== prefill_cap), BENCH_PAGED_SLOTS (paged-side slot count, default
4 x BENCH_SLOTS).

--chunked runs the TOKEN-BUDGET (chunked prefill) overload A/B: a
long-prompt Poisson mix (the regime where one prompt's prefill holds
the decode gang hostage) at 2x offered load, the chunked engine
(default token_budget) vs the legacy PHASE-prefill engine
(token_budget=0) at equal compiled shape and the SAME arrivals —
reporting TTFT p50/p90/p99 straight from engine metrics() (no
out-of-band percentile math), the p99/p50 flatness ratio, tokens/s,
budget utilization, an exact greedy chunked-vs-phase token-parity
check, and the zero-retrace contract. It also runs the FLAT-vs-row
A/B (ISSUE 13): the token-flattened [T] dispatch
(PADDLE_SERVING_FLAT_BUDGET) against the row-aligned [B, C] block at
the SAME arrivals, recording budget_padding_tokens for both (the
wasted-position collapse the flat layout exists for), with an exact
greedy flat-vs-row parity gate and exit 1 on
any post-warmup retrace. Its knobs: BENCH_TOKEN_BUDGET
(default: the engine default B x decode_chunk), BENCH_CHUNKED_LONG
(long-prompt fraction, default 0.6).

--cluster runs the CLUSTER ROUTER A/B: N in-process replicas (each a
full ServingEngine + private prefix cache over the SAME weights,
driven unthreaded so the whole cluster runs on one virtual clock)
behind serving_cluster.Router, on the SAME fixed-seed shared-template
Poisson arrivals — round_robin vs prefix_affinity (+ queue-depth
spill). Reported: delivered tokens/s, arrival-anchored TTFT p50/p99,
per-replica prefix hit-rate (the affinity win: each template's radix
chain concentrates on its ring owner instead of cold-missing on every
replica), per-replica zero-retrace, and a mid-bench replica-KILL drill
(prefix_affinity, same arrivals): recovery window from kill to the
last stranded request finishing elsewhere, with greedy token parity
against the no-kill run. Its knobs: BENCH_CLUSTER_REPLICAS (3),
BENCH_CLUSTER_KILL_AT (submission index triggering the kill, default
half the workload), BENCH_CLUSTER_SPILL_DEPTH (default 4 x slots — the
interactive default of 4 turns affinity into least-loaded under a
sustained backlog). The kill run additionally exports the MERGED
cluster Perfetto trace (serving_cluster.export_cluster_trace) and
FAILS unless it validates with the failed-over request joined across
two replicas at attempts 1 and 2 (BENCH_CLUSTER_TRACE_PATH keeps the
artifact), and records an "slo" goodput block — per-replica
ok/violated_queue/violated_service verdicts + queue/service
percentiles against the BENCH_SLO_TTFT_S/ITL_S/E2E_S objectives
(unset = no objectives; the accounting still reconciles).

--cluster ALSO runs the SCALE CHAOS DRILL (1 -> 3 -> 1): one replica
takes the 3-replica-rate arrivals, the Autoscaler grows the set on
queue-depth signals, a mid-load graceful drain of the busiest replica
LIVE-MIGRATES its in-flight streams (KV blocks + sampler state ship
replica-to-replica; BENCH_CLUSTER_DRAIN_AT picks the trigger index),
and the tail drains the set back to one. The bench exits non-zero
unless: greedy token parity vs the no-scale 1-replica run holds for
every request, zero streams dropped/orphaned, at least one live
migration happened with ZERO aborts, migrated slots recomputed ZERO
prefill tokens (engines' prefill_tokens_computed sum measured around
every drain), the set actually reached 3 and returned to 1, and every
engine — spawned replicas included — stayed at zero retraces.

--mesh runs the TENSOR-PARALLEL serving A/B: the SAME paged engine
single-device (mp=1) vs sharded over an mp=2 mesh (head-sharded KV
block pool, shard_map paged kernels), same fixed-seed Poisson
workload at the SAME arrivals. On CPU hosts the mesh is forced via
XLA_FLAGS=--xla_force_host_platform_device_count (honesty: forced
host "devices" share one physical CPU, so tokens/s measures dispatch
overhead, not a real TP speedup — the gates are the point). Exits
non-zero unless: exact greedy token parity mp=2 vs mp=1 for EVERY
request, zero retraces after warmup on the sharded engine, and the
per-device pool residency reconciles (kv_shard_pool_bytes x mp ==
the mp=1 engine's whole pool). Its knob: BENCH_MESH_MP (default 2).

--mesh-weights runs the WEIGHT-SHARDING A/B on the same mesh setup:
the identical paged engine mp=1 (weights dense on one device) vs
sharded over an mp-way mesh where the stacked layer params are placed
per generation.STACKED_PARAM_SPECS (column-parallel qkv/f1, row-
parallel out-proj/f2, sharded LM head), SAME weights, SAME fixed-seed
arrivals. Exits non-zero unless: exact greedy token parity for EVERY
request, zero retraces after warmup sharded, and the weight-residency
identity reconciles — (weight_bytes_per_device - weight_bytes_
replicated) x mp + weight_bytes_replicated == the mp=1 engine's dense
weight bytes (i.e. the sharded portion holds exactly 1/mp per
device). Knob: BENCH_MESH_MP; PADDLE_SERVING_MESH_WEIGHTS=0 would
disable the sharding under test (don't).

--qos runs the OVERLOAD QoS chaos drill: one paged engine at 2x its
measured capacity, mixed-class (high/normal/low) fixed-seed Poisson
traffic. Under that pressure the scheduler must degrade GRACEFULLY:
strictly better arrivals preempt running low-class slots to host RAM
and the parked sessions resume when pressure clears. Exits non-zero
unless: exact greedy token parity for EVERY request vs an unloaded
oracle run of the same workload (preemption/park/resume and the
weighted-fair packer never corrupt a stream), at least one preemption
actually fired with ZERO aborted/expired/dropped admitted requests,
the high class stayed inside its SLO (p99 TTFT within
BENCH_QOS_SLO_X, default 4x, of the unloaded p99) while the low class
measurably degraded past it, and zero retraces after warmup (every
QoS decision is pure host data). Knobs: BENCH_QOS_LOAD (default 2.0),
BENCH_QOS_SLO_X.

--disagg runs the DISAGGREGATED prefill/decode A/B: the SAME
fixed-seed long/short Poisson mix (the interference regime: long
prompt prefills stall co-resident decodes) on (a) one MIXED engine
and (b) a role-split cluster — one PREFILL replica + one DECODE
replica at EQUAL total slots — with streamed KV handoff
(handoff_blocks=1: committed prompt blocks ship while the prefill
tail runs). Reported per side: delivered tokens/s, arrival-anchored
TTFT p50/p99, decode ITL p50/p99 (inter-token gap once the stream
started — the interference metric disaggregation exists to fix), the
SLO verdict split, handoff/transfer counters, prefill accounting.
Exits non-zero unless: exact greedy token parity per request vs the
mixed run, zero drops/orphans/failovers, every session actually
handed off, ZERO prompt recompute (the decode engine computed no
prefill tokens AND cluster-wide computed+saved == submitted prompt
tokens — needs the prefix pool configured), zero retraces after
warmup on BOTH roles, and decode ITL p99 <= BENCH_DISAGG_ITL_X x the mixed run's
(default 4.0: the single-process driver serializes the two engines,
so a decode gap can carry a prefill pump the roles would overlap on
real split hardware — decode ITL p50 runs at parity and the p99
ratio is recorded for trending; the gate trips on gross regression,
e.g. a handoff stalling the decode loop). Knobs: BENCH_DISAGG_ITL_X,
BENCH_DISAGG_HANDOFF_BLOCKS (1), BENCH_CHUNKED_LONG (long-prompt
fraction, 0.4 here), BENCH_SLOTS (per-role slot count; mixed gets
2x).

--gray runs the GRAY-FAILURE chaos drill: 3 in-process replicas on
one virtual clock, round_robin routing (queue-blind on purpose: a
fresh-snapshot least_loaded policy quietly routes around a slow
replica's standing queue, masking the defense stack the drill is
about), and one replica injected
SLOW-BUT-ALIVE mid-bench (its pump only steps every
BENCH_GRAY_SLOW_FACTOR-th call while the heartbeat keeps beating —
the failure the heartbeat sweep can NOT see), lifted after the
measured stream drains (BENCH_GRAY_LIFT_AT < the request count lifts
mid-bench instead). Three runs at the SAME fixed-seed arrivals:
healthy baseline,
gray + defense (health scoring, circuit breaker, hedged dispatch on),
gray + defense OFF. Exits non-zero unless: exact greedy token parity
for EVERY request in both gray runs vs the healthy run (this is also
the hedge double-billing gate — a loser leg's tokens entering the
stream would break equality), zero drops/orphans, zero failovers and
zero deaths (gray must be shed, never declared dead), the breaker
actually OPENED and at least one hedge WON during the defense run,
the victim's breaker RE-CLOSED after the slowness lifted, defense
TTFT p99 <= 0.5x the no-defense p99 (the tail-at-scale payoff), the
injection really hurt the undefended run (no-defense p99 >= 1.5x
healthy), and zero retraces after warmup everywhere. Knobs:
BENCH_GRAY_SLOW_FACTOR (400), BENCH_GRAY_REQUESTS (48),
BENCH_GRAY_SLOW_AT / BENCH_GRAY_LIFT_AT (submission indices, default
1/3 of the workload and end-of-stream), BENCH_GRAY_LOAD (0.1 of probed
capacity ~ 1/3 of the drive loop's real capacity: gray defense is a
tail story and a backlog buries it).

All modes merge into ONE BENCH_serving.json (the shared-prompt record
lands under "shared_prompts", the spec record under "spec_decode",
the paged record under "paged_kv", the chunked-prefill record under
"chunked_prefill", the cluster record under "cluster", the mesh
record under "mesh_serving", the weight-sharding A/B under
"mesh_weights", the QoS overload record under "qos",
the disaggregated A/B under "disagg", the gray-failure drill under
"gray_failure"; each mode preserves the others' records).
"""
from __future__ import annotations

import json
import os
import sys
import time


class VirtualClock:
    """perf_counter plus a skip offset: drivers jump over idle waits for
    future arrivals instead of sleeping, so the bench measures compute,
    not sleep — while TTFT/latency still account queueing delay."""

    def __init__(self):
        self._t0 = time.perf_counter()
        self._skip = 0.0

    def now(self):
        return time.perf_counter() - self._t0 + self._skip

    def skip_to(self, t):
        n = self.now()
        if t > n:
            self._skip += t - n


def _make_workload(rng, n, v, smax):
    """Mixed-length requests: short-to-medium prompts, a long-tailed
    spread of generation lengths (high variance in decode length is what
    separates continuous from static batching — a static batch pads
    every row to its slowest member)."""
    reqs = []
    for _ in range(n):
        plen = int(rng.randint(4, 33))
        max_new = int(rng.choice([8, 16, 24, 32, 48, 64, 96],
                                 p=[.15, .20, .15, .15, .15, .12, .08]))
        max_new = min(max_new, smax - plen)
        prompt = rng.randint(1, v, (plen,)).astype("int32")
        reqs.append((prompt, max_new))
    return reqs


def _drive_continuous(eng, clock, reqs, arrivals):
    from paddle_tpu.inference.serving import AdmissionFull
    sub = {}                 # rid -> (workload index, submit time)
    i = 0
    while i < len(reqs) or eng.has_work:
        now = clock.now()
        while i < len(reqs) and arrivals[i] <= now:
            prompt, max_new = reqs[i]
            try:
                rid = eng.submit(prompt, max_new_tokens=max_new)
            except AdmissionFull:
                # honest backpressure (an explicitly sized paged pool,
                # or max_pending): back off, retry after the next step
                # — TTFT is measured from ARRIVAL, so the wait counts
                break
            sub[rid] = (i, clock.now())
            i += 1
        if not eng.has_work:
            clock.skip_to(arrivals[i])
            continue
        eng.step()
    return sub


def _drive_static(eng, clock, reqs, arrivals):
    """Gang scheduling: batches of num_slots in arrival order; a batch
    starts only when complete AND the engine is idle."""
    b = eng.num_slots
    sub = {}
    for s in range(0, len(reqs), b):
        batch = list(range(s, min(s + b, len(reqs))))
        clock.skip_to(max(arrivals[j] for j in batch))
        for j in batch:
            prompt, max_new = reqs[j]
            sub[eng.submit(prompt, max_new_tokens=max_new)] = (
                j, clock.now())
        eng.run()
    return sub


def _collect(eng, sub, arrivals):
    """Per-request TTFT/latency measured from ARRIVAL (queueing delay
    included): arrival -> submit is driver bookkeeping, submit -> first
    token comes from the engine record."""
    ttft, lat, toks = [], [], 0
    for rid, (j, t_sub) in sub.items():
        r = eng.results[rid]
        wait = t_sub - arrivals[j]
        ttft.append(wait + r["ttft_s"])
        lat.append(wait + r["latency_s"])
        toks += int(r["tokens"].size)
    return ttft, lat, toks


_SUB_RECORDS = ("shared_prompts", "spec_decode", "paged_kv",
                "chunked_prefill", "cluster", "mesh_serving",
                "mesh_weights", "qos", "disagg", "gray_failure",
                "quantized")


def _write_merged(path, record, sub_key=None, sub_rec=None):
    """ONE BENCH_serving.json for every mode: the classic record is the
    top level; the shared-prompt and spec-decode records ride under
    their own keys (`sub_key`). Whichever mode runs preserves the other
    modes' halves."""
    old = {}
    try:
        with open(path) as f:
            old = json.load(f)
    except (OSError, ValueError):
        pass
    if not isinstance(old, dict):
        old = {}
    if record is None:                   # sub-record mode: keep the rest
        record = old
    else:                                # classic mode: keep sub-records
        record = dict(record)
        for k in _SUB_RECORDS:
            if k in old and k not in record:
                record[k] = old[k]
    if sub_key is not None:
        record = dict(record, **{sub_key: sub_rec})
    try:
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    except OSError as e:
        print(f"bench_serving: could not write {path}: {e}",
              file=sys.stderr)
    return record


def _telemetry_block(eng, on_rec, off_rec):
    """The classic record's telemetry block: histogram percentiles,
    budget waste, step counts, the telemetry on-vs-off throughput ratio
    at the same arrivals, and a validity check of the Chrome-trace
    export of the measured window (the engine's rings were reset after
    warmup, so the export covers exactly what was measured)."""
    import tempfile

    from paddle_tpu.inference.telemetry import (export_chrome_tracing,
                                                validate_chrome_trace)
    m = eng.metrics()
    trace_valid = False
    spans = counters = 0
    fd, path = tempfile.mkstemp(suffix=".json",
                                prefix="bench_serving_trace_")
    os.close(fd)
    try:
        export_chrome_tracing(eng, path)
        doc = validate_chrome_trace(path)       # raises on bad structure
        evs = doc["traceEvents"]
        spans = sum(1 for e in evs if e.get("ph") == "X"
                    and str(e.get("name", "")).startswith("req ")
                    and "[finished]" in e["name"])
        counters = sum(1 for e in evs if e.get("ph") == "C"
                       and e.get("name") == "kv_blocks_used")
        trace_valid = spans >= 1 and (counters >= 1 or not eng.paged)
    except Exception as e:
        print(f"bench_serving: chrome-trace export failed: {e!r}",
              file=sys.stderr)
    finally:
        try:
            os.remove(path)
        except OSError:
            pass

    def ms(v):
        return None if v is None else round(1e3 * v, 1)

    tb = eng.token_budget
    return {
        "ring": eng.telemetry.ring,
        "tokens_per_sec_on": on_rec["tokens_per_sec"],
        "tokens_per_sec_off": off_rec["tokens_per_sec"],
        "on_over_off": round(on_rec["tokens_per_sec"]
                             / max(off_rec["tokens_per_sec"], 1e-9), 3),
        "ttft_p50_ms": ms(m["ttft_p50_s"]),
        "ttft_p90_ms": ms(m["ttft_p90_s"]),
        "ttft_p99_ms": ms(m["ttft_p99_s"]),
        "latency_p50_ms": ms(m["latency_p50_s"]),
        "latency_p99_ms": ms(m["latency_p99_s"]),
        "budget_steps": m["budget_steps"],
        "budget_tokens_used": m["budget_tokens_used"],
        # real computed-position waste from the engine counter (was a
        # steps x budget - used proxy before budget_padding_tokens
        # existed; the counter is exact under both layouts)
        "budget_tokens_wasted": m["budget_padding_tokens"] if tb else 0,
        "budget_utilization": m["budget_utilization"],
        "step_events": len(eng.telemetry.steps),
        "request_spans": len(eng.telemetry.spans),
        "chrome_trace_request_spans": spans,
        "chrome_trace_kv_counter_events": counters,
        "chrome_trace_valid": trace_valid,
    }


def _require_tpu():
    """Every timed mode starts here: a TPU or a non-zero exit (the shared
    helper in paddle_tpu.device.chip — no CPU fallback), and the compile
    cache placed from outside. Returns (jax, device)."""
    import jax

    from paddle_tpu.device.chip import require_tpu, use_compile_cache
    dev = require_tpu()
    use_compile_cache()
    return jax, dev


def _pin_virtual_cpu_mesh():
    """--mesh / --mesh-weights are COUNT drills (parity, retraces, byte
    identities over a forced 8-device host mesh), not measurements: they
    pin the CPU on purpose, stay off the require-TPU helper, and write
    no rate or latency field. Must run before the first jax import.
    Returns (jax, device)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    return jax, jax.devices()[0]


def _device_record(dev):
    from paddle_tpu.device.chip import device_record
    return device_record(dev)


def _build_model(dims=None):
    """GPT-2-124M-width serving stack in bf16; ``dims`` (the CPU mesh
    drills' small fp32 model) overrides the widths."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import FusedMultiTransformer
    from paddle_tpu.nn.layer.common import Embedding, Linear

    E, H, FF, L, V = dims or (768, 12, 3072, 12, 50304)
    paddle.seed(0)
    embed = Embedding(V, E)
    fmt = FusedMultiTransformer(E, H, FF, num_layers=L,
                                normalize_before=True)
    head = Linear(E, V, bias_attr=False)
    if dims is None:
        for lay in (embed, fmt, head):
            lay.bfloat16()
    fmt.eval()
    return fmt, embed, head, (E, H, FF, L, V)


def main(argv=None):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--shared-prompts" in argv:
        return main_shared_prompts()
    if "--spec" in argv:
        return main_spec()
    if "--paged" in argv:
        return main_paged()
    if "--chunked" in argv:
        return main_chunked()
    if "--cluster" in argv:
        return main_cluster()
    if "--mesh-weights" in argv:
        return main_mesh_weights()
    if "--quant" in argv:
        return main_quant()
    if "--mesh" in argv:
        return main_mesh()
    if "--qos" in argv:
        return main_qos()
    if "--disagg" in argv:
        return main_disagg()
    if "--gray" in argv:
        return main_gray()
    jax, dev = _require_tpu()
    import numpy as np

    from paddle_tpu.inference.serving import ServingEngine

    slots = int(os.environ.get("BENCH_SLOTS", "8"))
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "4"))
    n_warm = int(os.environ.get("BENCH_SERVE_WARMUP", str(2 * slots)))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS", str(6 * slots)))
    load = float(os.environ.get("BENCH_SERVE_LOAD", "1.5"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))

    fmt, embed, head, (E, H, FF, L, V) = _build_model()

    rng = np.random.RandomState(seed)
    # bucket_reqs cover every prefill bucket a 4..32-token prompt can
    # round up to (4, 8, 16, 32) — submitted ONE AT A TIME during warmup
    # so each bucket's executable compiles (a gang admission would share
    # the largest bucket); the measured phase asserts ZERO retraces
    bucket_reqs = [(rng.randint(1, V, (plen,)).astype("int32"), 4)
                   for plen in (4, 8, 16, 32)]
    warm_reqs = _make_workload(rng, n_warm, V, smax)
    meas_reqs = _make_workload(rng, n_meas, V, smax)

    def run_mode(drive, label, telemetry_ring=None, arrivals=None):
        clock = VirtualClock()
        eng = ServingEngine(fmt, embed, head, num_slots=slots,
                            max_seq_len=smax, decode_chunk=chunk,
                            clock=clock.now, telemetry_ring=telemetry_ring)
        # ---- warmup pass 1: compiles (each prefill bucket admitted
        # solo); pass 2 (all compiled): capacity estimate used to set
        # the Poisson rate — including compile time would understate
        # capacity and undersubmit the measured phase
        for prompt, max_new in bucket_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
            eng.run()
        for prompt, max_new in warm_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        eng.reset_metrics(keep_results=False)
        t0 = clock.now()
        for prompt, max_new in warm_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        warm = eng.metrics()
        cap = warm["tokens_emitted"] / max(clock.now() - t0, 1e-9)
        traces_warm = warm["traces"]
        eng.reset_metrics(keep_results=False)

        # ---- measured phase: Poisson arrivals at `load` x capacity
        # (relative offsets so a re-run can replay the SAME arrivals —
        # the telemetry on/off A/B rides the telemetry-on schedule)
        if arrivals is None:
            mean_new = float(np.mean([m for _, m in meas_reqs]))
            rate = load * cap / mean_new          # requests / s
            arr_rng = np.random.RandomState(seed + 1)
            arrivals = np.cumsum(
                arr_rng.exponential(1.0 / rate, size=len(meas_reqs)))
        arr = arrivals + clock.now()

        t_start = clock.now()
        sub = drive(eng, clock, meas_reqs, arr)
        elapsed = clock.now() - t_start
        ttft, lat, toks = _collect(eng, sub, arr)
        m = eng.metrics()
        return {
            "label": label,
            "tokens": toks,
            "tokens_per_sec": round(toks / max(elapsed, 1e-9), 2),
            "elapsed_s": round(elapsed, 3),
            "capacity_tokens_per_sec": round(cap, 2),
            "retraces_after_warmup": m["traces"] - traces_warm,
            "requests": len(meas_reqs),
            "ttft_p50_ms": round(1e3 * float(np.percentile(ttft, 50)), 1),
            "ttft_p99_ms": round(1e3 * float(np.percentile(ttft, 99)), 1),
            "latency_p50_ms": round(1e3 * float(np.percentile(lat, 50)),
                                    1),
            "latency_p99_ms": round(1e3 * float(np.percentile(lat, 99)),
                                    1),
        }, arrivals, eng

    # telemetry overhead A/B: the BASELINE (ring disabled) runs first
    # and its capacity sets the arrival schedule — the telemetry-on
    # engine then drains the SAME arrivals and must stay within a few %
    # (the ratio is recorded in the telemetry block); the true
    # overhead is host-side bookkeeping only.
    cont_off, arrivals, _ = run_mode(_drive_continuous,
                                     "continuous_tele_off",
                                     telemetry_ring=0)
    cont, _, eng_cont = run_mode(_drive_continuous, "continuous",
                                 arrivals=arrivals)
    stat, _, _ = run_mode(_drive_static, "static")
    telemetry_block = _telemetry_block(eng_cont, cont, cont_off)

    record = {
        "metric": "serving_continuous_tokens_per_sec",
        "value": cont["tokens_per_sec"],
        "unit": "tokens/s",
        "static_tokens_per_sec": stat["tokens_per_sec"],
        "speedup_vs_static": round(
            cont["tokens_per_sec"] / max(stat["tokens_per_sec"], 1e-9),
            3),
        "num_slots": slots, "max_seq": smax, "decode_chunk": chunk,
        "layers": L, "hidden": E, "vocab": V,
        "requests": n_meas, "warmup_requests": n_warm,
        "offered_load": load,
        "retraces_after_warmup": cont["retraces_after_warmup"],
        "ttft_p50_ms": cont["ttft_p50_ms"],
        "ttft_p99_ms": cont["ttft_p99_ms"],
        "latency_p50_ms": cont["latency_p50_ms"],
        "latency_p99_ms": cont["latency_p99_ms"],
        "static_ttft_p50_ms": stat["ttft_p50_ms"],
        "static_ttft_p99_ms": stat["ttft_p99_ms"],
        "static_latency_p50_ms": stat["latency_p50_ms"],
        "static_latency_p99_ms": stat["latency_p99_ms"],
        "device": _device_record(dev),
        "cache_mode": ("int8" if os.environ.get(
            "PADDLE_TPU_DECODE_INT8_CACHE") == "1" else "fp"),
        "telemetry": telemetry_block,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    # merge only for the FILE (preserving the shared_prompts half): the
    # TPU window entry and stdout stay the pure classic record — a
    # stale shared-prompt sub-record must not ride along
    _write_merged(path, record)
    print(json.dumps(record))
    rc = 0
    if record["retraces_after_warmup"]:
        print("bench_serving: RETRACES AFTER WARMUP — the fixed-shape "
              "contract is broken", file=sys.stderr)
        rc = 1
    if not telemetry_block["chrome_trace_valid"]:
        print("bench_serving: CHROME-TRACE EXPORT of the measured "
              "window is invalid (no complete request span / counter "
              "track)", file=sys.stderr)
        rc = 1
    if telemetry_block["on_over_off"] < 0.97:
        # recorded AND flagged: telemetry must stay within 3% of off
        print(f"bench_serving: telemetry overhead exceeds budget — "
              f"on/off tokens/s ratio "
              f"{telemetry_block['on_over_off']} < 0.97",
              file=sys.stderr)
    return rc


def _make_shared_workload(rng, n, v, smax, templates, sfx_lo, sfx_hi,
                          new_choices):
    """Shared-system-prompt traffic: each request is one of N templates
    (the shared prefix — system prompt / few-shot header) plus a short
    unique user suffix; generation lengths are short-to-medium (the
    TTFT-sensitive interactive regime where redundant prefill dominates)."""
    import numpy as np
    reqs = []
    for _ in range(n):
        t = templates[int(rng.randint(len(templates)))]
        sfx = rng.randint(1, v, (int(rng.randint(sfx_lo, sfx_hi + 1)),)
                          ).astype("int32")
        prompt = np.concatenate([t, sfx])
        max_new = int(rng.choice(new_choices))
        reqs.append((prompt, min(max_new, smax - prompt.size)))
    return reqs


def main_shared_prompts():
    """Prefix-cache A/B: the same engine class, same compiled shapes,
    same fixed-seed Poisson shared-prompt workload and the same arrival
    times — with the prefix cache ON vs OFF. The arrival rate comes from
    the cache-OFF engine's measured capacity, so the ON side's win shows
    up as BOTH higher delivered tokens/s and lower TTFT (it drains the
    same backlog faster)."""
    jax, dev = _require_tpu()
    import numpy as np

    from paddle_tpu.inference.serving import ServingEngine

    slots = int(os.environ.get("BENCH_SLOTS", "8"))
    # a longer ring than the classic mode: the shared-prompt regime is
    # exactly the long-system-prompt + short-answer traffic shape
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "4"))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS", str(6 * slots)))
    load = float(os.environ.get("BENCH_SERVE_LOAD", "1.5"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))
    n_templates = int(os.environ.get("BENCH_PREFIX_TEMPLATES", "4"))
    tlen = int(os.environ.get("BENCH_PREFIX_TLEN",
                              "512"))
    # 64 on CPU too (not the old 16): the paged engine's per-token
    # attend runs the paged kernel at Smax/Bt grid steps — at Bt=16 the
    # interpret-mode grid overhead swamps the prefill savings and the
    # A/B under-reports (0.89x measured; 1.19-1.38x across runs at
    # Bt=64). Templates still span 3 blocks, so pool churn stays
    # exercised.
    cap_ = int(os.environ.get("BENCH_PREFIX_CAP", "64"))
    pool_blocks = int(os.environ.get("BENCH_PREFIX_BLOCKS",
                                     str(4 * n_templates * (tlen // cap_))))
    new_choices = [8, 12, 16]
    sfx_lo, sfx_hi = 3, min(8, smax - tlen - max(new_choices))
    if sfx_hi < sfx_lo:
        print(f"bench_serving: BENCH_PREFIX_TLEN={tlen} leaves no room "
              f"in BENCH_SMAX={smax} for a suffix + {max(new_choices)} "
              f"generated tokens (need tlen <= smax - "
              f"{sfx_lo + max(new_choices)})", file=sys.stderr)
        return 2

    fmt, embed, head, (E, H, FF, L, V) = _build_model()

    rng = np.random.RandomState(seed)
    templates = [rng.randint(1, V, (tlen,)).astype("int32")
                 for _ in range(n_templates)]
    # warmup covers every executable either side will need: one request
    # PER TEMPLATE admitted solo (miss path: bulk bucket + commits),
    # then a re-run of the same prompts (hit path: adopt ladder + every
    # suffix-scan chunk bucket), plus suffix-length extremes
    warm_reqs = _make_shared_workload(
        rng, max(2 * slots, 2 * n_templates), V, smax, templates,
        sfx_lo, sfx_hi, new_choices)
    meas_reqs = _make_shared_workload(rng, n_meas, V, smax, templates,
                                      sfx_lo, sfx_hi, new_choices)

    def run_mode(cache_on, arrivals=None):
        clock = VirtualClock()
        eng = ServingEngine(
            fmt, embed, head, num_slots=slots, max_seq_len=smax,
            decode_chunk=chunk, clock=clock.now, prefill_cap=cap_,
            prefix_cache_blocks=pool_blocks if cache_on else 0)
        # solo admissions compile every bucket BOTH paths need: the
        # first request per template is a MISS (bulk bucket + commit),
        # the repeats are HITS at the suffix-length extremes (adopt
        # ladder + each suffix-scan chunk bucket — a miss never touches
        # the scan, so the hit path must be warmed explicitly)
        for t in templates:
            for sfx in (sfx_lo, sfx_lo, sfx_hi):
                p = np.concatenate([t, np.arange(1, sfx + 1,
                                                 dtype=np.int32)])
                eng.submit(p, max_new_tokens=4)
                eng.run()
        for prompt, max_new in warm_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        eng.reset_metrics(keep_results=False)
        t0 = clock.now()
        for prompt, max_new in warm_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        warm = eng.metrics()
        cap_tps = warm["tokens_emitted"] / max(clock.now() - t0, 1e-9)
        traces_warm = warm["traces"]
        eng.reset_metrics(keep_results=False)

        if arrivals is None:
            mean_new = float(np.mean([m for _, m in meas_reqs]))
            rate = load * cap_tps / mean_new
            arr_rng = np.random.RandomState(seed + 1)
            arrivals = np.cumsum(
                arr_rng.exponential(1.0 / rate, size=len(meas_reqs)))
        arr = arrivals + clock.now()
        t_start = clock.now()
        sub = _drive_continuous(eng, clock, meas_reqs, arr)
        elapsed = clock.now() - t_start
        ttft, lat, toks = _collect(eng, sub, arr)
        m = eng.metrics()
        return {
            "cache": "on" if cache_on else "off",
            "tokens": toks,
            "tokens_per_sec": round(toks / max(elapsed, 1e-9), 2),
            "elapsed_s": round(elapsed, 3),
            "capacity_tokens_per_sec": round(cap_tps, 2),
            "retraces_after_warmup": m["traces"] - traces_warm,
            "prefix_hits": m["prefix_hits"],
            "prefix_misses": m["prefix_misses"],
            "prefix_hit_rate": m["prefix_hit_rate"],
            "prefill_tokens_saved": m["prefill_tokens_saved"],
            "prefill_tokens_computed": m["prefill_tokens_computed"],
            "ttft_p50_ms": round(1e3 * float(np.percentile(ttft, 50)), 1),
            "ttft_p99_ms": round(1e3 * float(np.percentile(ttft, 99)), 1),
            "latency_p50_ms": round(1e3 * float(np.percentile(lat, 50)),
                                    1),
            "latency_p99_ms": round(1e3 * float(np.percentile(lat, 99)),
                                    1),
        }, arrivals

    off, arrivals = run_mode(False)
    on, _ = run_mode(True, arrivals)

    record = {
        "metric": "serving_prefix_cache_speedup",
        "value": round(on["tokens_per_sec"]
                       / max(off["tokens_per_sec"], 1e-9), 3),
        "unit": "x tokens/s vs prefix-cache-off",
        "tokens_per_sec_on": on["tokens_per_sec"],
        "tokens_per_sec_off": off["tokens_per_sec"],
        "ttft_p50_ms_on": on["ttft_p50_ms"],
        "ttft_p50_ms_off": off["ttft_p50_ms"],
        "ttft_p99_ms_on": on["ttft_p99_ms"],
        "ttft_p99_ms_off": off["ttft_p99_ms"],
        "latency_p50_ms_on": on["latency_p50_ms"],
        "latency_p50_ms_off": off["latency_p50_ms"],
        "prefix_hit_rate": on["prefix_hit_rate"],
        "prefix_hits": on["prefix_hits"],
        "prefix_misses": on["prefix_misses"],
        "prefill_tokens_saved": on["prefill_tokens_saved"],
        "prefill_tokens_computed": on["prefill_tokens_computed"],
        "retraces_after_warmup": on["retraces_after_warmup"],
        "retraces_after_warmup_off": off["retraces_after_warmup"],
        "num_slots": slots, "max_seq": smax, "decode_chunk": chunk,
        "prefill_cap": cap_, "prefix_cache_blocks": pool_blocks,
        "templates": n_templates, "template_tokens": tlen,
        "layers": L, "hidden": E, "vocab": V,
        "requests": n_meas, "offered_load": load, "seed": seed,
        "device": _device_record(dev),
        "cache_mode": ("int8" if os.environ.get(
            "PADDLE_TPU_DECODE_INT8_CACHE") == "1" else "fp"),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "shared_prompts", record)
    print(json.dumps(record))
    if record["retraces_after_warmup"]:
        print("bench_serving: RETRACES AFTER WARMUP with the prefix "
              "cache on — the fixed-shape contract is broken",
              file=sys.stderr)
        return 1
    return 0


def _make_repetitive_workload(rng, n, v, smax, new_choices):
    """Repetitive-output traffic (summarize / echo / extract prompt
    shapes): each prompt is a short content core tiled a few times —
    the regime prompt-lookup drafting targets, where the output copies
    spans of the input or of its own earlier output. Generations run
    long enough (32..64) for the model's decode to settle into its
    repeating continuation, which is exactly what the n-gram drafter
    then proposes."""
    import numpy as np
    reqs = []
    for _ in range(n):
        core = rng.randint(1, v, (int(rng.randint(6, 13)),)
                           ).astype("int32")
        prompt = np.tile(core, int(rng.randint(2, 4)))
        max_new = int(rng.choice(new_choices))
        reqs.append((prompt, min(max_new, smax - prompt.size)))
    return reqs


def main_spec():
    """Speculative-decoding A/B: the same engine class, same compiled
    shapes, same fixed-seed Poisson repetitive-output workload and the
    SAME arrival times — with the n-gram drafter + verify step ON
    (spec_k=BENCH_SPEC_K) vs OFF (spec_k=0). The arrival rate comes
    from the spec-OFF engine's measured capacity, so the ON side's win
    shows up as higher delivered tokens/s draining the same backlog.
    The record lands under "spec_decode" in BENCH_serving.json (other
    modes' records preserved)."""
    jax, dev = _require_tpu()
    import numpy as np

    from paddle_tpu.inference.serving import ServingEngine

    slots = int(os.environ.get("BENCH_SLOTS", "8"))
    # a longer ring than the classic mode: repetitive-output traffic
    # needs generations long enough for the repetition to establish
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "4"))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS", str(6 * slots)))
    load = float(os.environ.get("BENCH_SERVE_LOAD", "1.5"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))
    spec_k = int(os.environ.get("BENCH_SPEC_K", "8"))
    new_choices = [48, 64, 96]

    # speculative decoding pays off where a K+1-wide pass costs about
    # one 1-wide pass (weights/cache streamed once per step)
    fmt, embed, head, (E, H, FF, L, V) = _build_model()

    rng = np.random.RandomState(seed)
    # solo admissions covering every prefill bucket a 12..36-token
    # repetitive prompt rounds up to (16, 32, 64) — same warmup
    # discipline as the classic mode
    bucket_reqs = [(np.tile(rng.randint(1, V, (p // 2,)).astype("int32"),
                            2), 8)
                   for p in (12, 24, 36)]
    warm_reqs = _make_repetitive_workload(rng, 2 * slots, V, smax,
                                          new_choices)
    meas_reqs = _make_repetitive_workload(rng, n_meas, V, smax,
                                          new_choices)

    def run_mode(k, arrivals=None):
        clock = VirtualClock()
        eng = ServingEngine(fmt, embed, head, num_slots=slots,
                            max_seq_len=smax, decode_chunk=chunk,
                            clock=clock.now, spec_k=k)
        for prompt, max_new in bucket_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
            eng.run()
        for prompt, max_new in warm_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        eng.reset_metrics(keep_results=False)
        t0 = clock.now()
        for prompt, max_new in warm_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        warm = eng.metrics()
        cap = warm["tokens_emitted"] / max(clock.now() - t0, 1e-9)
        traces_warm = warm["traces"]
        eng.reset_metrics(keep_results=False)

        if arrivals is None:
            mean_new = float(np.mean([m for _, m in meas_reqs]))
            rate = load * cap / mean_new
            arr_rng = np.random.RandomState(seed + 1)
            arrivals = np.cumsum(
                arr_rng.exponential(1.0 / rate, size=len(meas_reqs)))
        arr = arrivals + clock.now()
        t_start = clock.now()
        sub = _drive_continuous(eng, clock, meas_reqs, arr)
        elapsed = clock.now() - t_start
        ttft, lat, toks = _collect(eng, sub, arr)
        m = eng.metrics()
        return {
            "spec": "on" if k else "off",
            "tokens": toks,
            "tokens_per_sec": round(toks / max(elapsed, 1e-9), 2),
            "elapsed_s": round(elapsed, 3),
            "capacity_tokens_per_sec": round(cap, 2),
            "retraces_after_warmup": m["traces"] - traces_warm,
            "draft_proposed": m["draft_proposed"],
            "draft_accepted": m["draft_accepted"],
            "acceptance_rate": m["acceptance_rate"],
            "tokens_per_step": m["tokens_per_step"],
            "decode_steps": m["decode_steps"],
            "ttft_p50_ms": round(1e3 * float(np.percentile(ttft, 50)), 1),
            "ttft_p99_ms": round(1e3 * float(np.percentile(ttft, 99)), 1),
            "latency_p50_ms": round(1e3 * float(np.percentile(lat, 50)),
                                    1),
            "latency_p99_ms": round(1e3 * float(np.percentile(lat, 99)),
                                    1),
        }, arrivals

    off, arrivals = run_mode(0)
    on, _ = run_mode(spec_k, arrivals)

    record = {
        "metric": "serving_spec_decode_speedup",
        "value": round(on["tokens_per_sec"]
                       / max(off["tokens_per_sec"], 1e-9), 3),
        "unit": "x tokens/s vs spec-off",
        "tokens_per_sec_on": on["tokens_per_sec"],
        "tokens_per_sec_off": off["tokens_per_sec"],
        "acceptance_rate": on["acceptance_rate"],
        "tokens_per_step": on["tokens_per_step"],
        "draft_proposed": on["draft_proposed"],
        "draft_accepted": on["draft_accepted"],
        "decode_steps_on": on["decode_steps"],
        "decode_steps_off": off["decode_steps"],
        "ttft_p50_ms_on": on["ttft_p50_ms"],
        "ttft_p50_ms_off": off["ttft_p50_ms"],
        "latency_p50_ms_on": on["latency_p50_ms"],
        "latency_p50_ms_off": off["latency_p50_ms"],
        "retraces_after_warmup": on["retraces_after_warmup"],
        "retraces_after_warmup_off": off["retraces_after_warmup"],
        "spec_k": spec_k,
        "num_slots": slots, "max_seq": smax, "decode_chunk": chunk,
        "layers": L, "hidden": E, "vocab": V,
        "requests": n_meas, "offered_load": load, "seed": seed,
        "device": _device_record(dev),
        "cache_mode": ("int8" if os.environ.get(
            "PADDLE_TPU_DECODE_INT8_CACHE") == "1" else "fp"),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "spec_decode", record)
    print(json.dumps(record))
    if record["retraces_after_warmup"]:
        print("bench_serving: RETRACES AFTER WARMUP with speculative "
              "decoding on — the fixed-shape contract is broken",
              file=sys.stderr)
        return 1
    return 0


def main_paged():
    """Paged-vs-dense capacity A/B at EQUAL KV MEMORY: the dense
    engine reserves B_dense x Smax positions up front; the paged
    engine gets a pool of exactly B_dense x Smax/Bt blocks (the same
    bytes) but 4x the slots — concurrency is bounded by actual token
    residency, so it runs more requests at once and drains the same
    overload backlog faster. Also runs an exact greedy paged-vs-dense
    token-parity check at equal shape, and asserts the zero-retrace
    contract on both sides. Lands under "paged_kv" in
    BENCH_serving.json (other modes' records preserved)."""
    jax, dev = _require_tpu()
    import numpy as np

    from paddle_tpu.inference.serving import AdmissionFull, ServingEngine

    slots_dense = int(os.environ.get("BENCH_SLOTS",
                                     "8"))
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "4"))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                str(6 * slots_dense)))
    load = float(os.environ.get("BENCH_SERVE_LOAD", "2.0"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))
    cap_ = int(os.environ.get("BENCH_PAGED_CAP", "64"))
    slots_paged = int(os.environ.get("BENCH_PAGED_SLOTS",
                                     str(4 * slots_dense)))
    pool_blocks = slots_dense * (smax // cap_)   # EQUAL KV bytes

    fmt, embed, head, (E, H, FF, L, V) = _build_model()

    rng = np.random.RandomState(seed)
    # short-to-medium requests (p + max_new << Smax): the regime where
    # dense slot reservation wastes most of its ring and paged
    # concurrency pays; every prefill bucket 8..32 gets a warmup rep
    def make(n):
        reqs = []
        for _ in range(n):
            plen = int(rng.randint(6, 25))
            max_new = int(rng.choice([16, 24, 32]))
            reqs.append((rng.randint(1, V, (plen,)).astype("int32"),
                         max_new))
        return reqs

    bucket_reqs = [(rng.randint(1, V, (p,)).astype("int32"), 4)
                   for p in (8, 16, 24)]
    warm_reqs = make(2 * slots_paged)
    meas_reqs = make(n_meas)

    def run_mode(paged, n_slots, bound_pool, arrivals=None):
        clock = VirtualClock()
        kw = dict(num_slots=n_slots, paged=paged)
        if bound_pool:
            kw["kv_pool_blocks"] = pool_blocks
        eng = ServingEngine(fmt, embed, head, max_seq_len=smax,
                            decode_chunk=chunk, clock=clock.now,
                            prefill_cap=cap_, **kw)
        for prompt, max_new in bucket_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
            eng.run()
        for prompt, max_new in warm_reqs:
            try:
                eng.submit(prompt, max_new_tokens=max_new)
            except AdmissionFull:        # bounded pool: drain, retry
                eng.run()
                eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        eng.reset_metrics(keep_results=False)
        t0 = clock.now()
        _drive_continuous(eng, clock, warm_reqs,
                          np.zeros(len(warm_reqs)) + clock.now())
        warm = eng.metrics()
        cap_tps = warm["tokens_emitted"] / max(clock.now() - t0, 1e-9)
        traces_warm = warm["traces"]
        eng.reset_metrics(keep_results=False)

        if arrivals is None:
            mean_new = float(np.mean([m for _, m in meas_reqs]))
            rate = load * cap_tps / mean_new
            arr_rng = np.random.RandomState(seed + 1)
            arrivals = np.cumsum(
                arr_rng.exponential(1.0 / rate, size=len(meas_reqs)))
        arr = arrivals + clock.now()
        t_start = clock.now()
        sub = _drive_continuous(eng, clock, meas_reqs, arr)
        elapsed = clock.now() - t_start
        ttft, lat, toks = _collect(eng, sub, arr)
        m = eng.metrics()
        max_conc = max((rec["occupancy"] for rec in eng.chunk_log),
                       default=0.0) * eng.num_slots
        return {
            "layout": "paged" if paged else "dense",
            "num_slots": eng.num_slots,
            "kv_blocks": pool_blocks if bound_pool else None,
            "kv_positions": (pool_blocks * cap_ if bound_pool
                             else eng.num_slots * smax),
            "max_concurrent_slots": round(max_conc, 1),
            "tokens": toks,
            "tokens_per_sec": round(toks / max(elapsed, 1e-9), 2),
            "elapsed_s": round(elapsed, 3),
            "capacity_tokens_per_sec": round(cap_tps, 2),
            "retraces_after_warmup": m["traces"] - traces_warm,
            "requests_rejected": m["requests_rejected"],
            "kv_cow_copies": m["kv_cow_copies"],
            "ttft_p50_ms": round(1e3 * float(np.percentile(ttft, 50)), 1),
            "ttft_p99_ms": round(1e3 * float(np.percentile(ttft, 99)), 1),
            "latency_p50_ms": round(1e3 * float(np.percentile(lat, 50)),
                                    1),
            "latency_p99_ms": round(1e3 * float(np.percentile(lat, 99)),
                                    1),
        }, arrivals

    # three engines, SAME arrivals: (1) the dense baseline; (2) paged
    # at EQUAL SLOT COUNT and default pool sizing — the per-step-cost
    # comparison (the paged layout must not tax throughput); (3) paged
    # at EQUAL KV MEMORY (pool == the dense engine's exact bytes) with
    # 4x the slots — the capacity win the layout exists for
    dense, arrivals = run_mode(False, slots_dense, False)
    eq_slots, _ = run_mode(True, slots_dense, False, arrivals)
    paged, _ = run_mode(True, slots_paged, True, arrivals)

    # exact greedy parity at EQUAL shape (the on/off token contract)
    par_reqs = make(2 * slots_dense)

    def parity_run(paged_flag):
        eng = ServingEngine(fmt, embed, head, num_slots=slots_dense,
                            max_seq_len=smax, decode_chunk=chunk,
                            prefill_cap=cap_, paged=paged_flag)
        rids = [eng.submit(p, max_new_tokens=m) for p, m in par_reqs]
        eng.run()
        return [eng.results[r]["tokens"].tolist() for r in rids]

    parity_ok = parity_run(True) == parity_run(False)

    record = {
        "metric": "serving_paged_kv_max_concurrent_ratio",
        "value": round(paged["max_concurrent_slots"]
                       / max(dense["max_concurrent_slots"], 1e-9), 3),
        "unit": "x concurrent slots vs dense at equal KV memory",
        "tokens_per_sec_paged": paged["tokens_per_sec"],
        "tokens_per_sec_dense": dense["tokens_per_sec"],
        "tokens_per_sec_ratio": round(
            paged["tokens_per_sec"]
            / max(dense["tokens_per_sec"], 1e-9), 3),
        # the per-step-cost gate: paged at the SAME slot count must be
        # within a few % of dense (the table gather is not a tax)
        "tokens_per_sec_equal_slots": eq_slots["tokens_per_sec"],
        "tokens_per_sec_ratio_equal_slots": round(
            eq_slots["tokens_per_sec"]
            / max(dense["tokens_per_sec"], 1e-9), 3),
        "max_concurrent_paged": paged["max_concurrent_slots"],
        "max_concurrent_dense": dense["max_concurrent_slots"],
        "kv_positions_budget": dense["kv_positions"],
        "kv_blocks": pool_blocks, "block_tokens": cap_,
        "num_slots_paged": slots_paged, "num_slots_dense": slots_dense,
        "parity_ok": parity_ok,
        "retraces_after_warmup": paged["retraces_after_warmup"],
        "retraces_after_warmup_dense": dense["retraces_after_warmup"],
        "requests_rejected_paged": paged["requests_rejected"],
        "kv_cow_copies": paged["kv_cow_copies"],
        "ttft_p50_ms_paged": paged["ttft_p50_ms"],
        "ttft_p50_ms_dense": dense["ttft_p50_ms"],
        "latency_p50_ms_paged": paged["latency_p50_ms"],
        "latency_p50_ms_dense": dense["latency_p50_ms"],
        "max_seq": smax, "decode_chunk": chunk,
        "layers": L, "hidden": E, "vocab": V,
        "requests": n_meas, "offered_load": load, "seed": seed,
        "device": _device_record(dev),
        "cache_mode": ("int8" if os.environ.get(
            "PADDLE_TPU_DECODE_INT8_CACHE") == "1" else "fp"),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "paged_kv", record)
    print(json.dumps(record))
    rc = 0
    if record["retraces_after_warmup"] or \
            eq_slots["retraces_after_warmup"]:
        print("bench_serving: RETRACES AFTER WARMUP with the paged KV "
              "cache — the fixed-shape contract is broken",
              file=sys.stderr)
        rc = 1
    if not parity_ok:
        print("bench_serving: PAGED/DENSE TOKEN PARITY BROKE",
              file=sys.stderr)
        rc = 1
    return rc


def main_mesh():
    """Tensor-parallel serving A/B: ONE paged ServingEngine sharded
    over an mp-way mesh (head-sharded KV block pool + shard_map paged
    kernels) vs the identical engine single-device, SAME weights, SAME
    fixed-seed Poisson arrivals. The gates ARE the result: exact
    greedy token parity per request, zero retraces after warmup on the
    sharded side, and per-device pool bytes == the mp=1 pool / mp.
    Lands under "mesh_serving" in BENCH_serving.json."""
    jax, dev = _pin_virtual_cpu_mesh()
    import numpy as np

    from paddle_tpu.inference.serving import AdmissionFull, ServingEngine
    from paddle_tpu.parallel import init_serving_mesh

    mp = int(os.environ.get("BENCH_MESH_MP", "2"))
    slots = int(os.environ.get("BENCH_SLOTS", "4"))
    smax = int(os.environ.get("BENCH_SMAX", "256"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "4"))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS", str(6 * slots)))
    load = float(os.environ.get("BENCH_SERVE_LOAD", "1.5"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))
    cap_ = int(os.environ.get("BENCH_PAGED_CAP", "32"))
    if jax.device_count() < mp:
        print(f"bench_serving: --mesh needs >= {mp} devices, found "
              f"{jax.device_count()}", file=sys.stderr)
        return 1

    # the drill's small fp32 model (H=8 divides mp=2 and 4)
    fmt, embed, head, (E, H, FF, L, V) = _build_model(
        dims=(256, 8, 1024, 4, 512))
    if H % mp:
        print(f"bench_serving: --mesh mp={mp} does not divide "
              f"num_heads={H}", file=sys.stderr)
        return 1

    rng = np.random.RandomState(seed)

    def make(n):
        reqs = []
        for _ in range(n):
            plen = int(rng.randint(6, 25))
            max_new = int(rng.choice([16, 24, 32]))
            reqs.append((rng.randint(1, V, (plen,)).astype("int32"),
                         max_new))
        return reqs

    bucket_reqs = [(rng.randint(1, V, (p,)).astype("int32"), 4)
                   for p in (8, 16, 24)]
    warm_reqs = make(2 * slots)
    meas_reqs = make(n_meas)

    def run_mode(label, arrivals=None):
        clock = VirtualClock()
        eng = ServingEngine(fmt, embed, head, num_slots=slots,
                            max_seq_len=smax, decode_chunk=chunk,
                            prefill_cap=cap_, paged=True,
                            clock=clock.now)
        for prompt, max_new in bucket_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
            eng.run()
        for prompt, max_new in warm_reqs:
            try:
                eng.submit(prompt, max_new_tokens=max_new)
            except AdmissionFull:
                eng.run()
                eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        eng.reset_metrics(keep_results=False)
        t0 = clock.now()
        _drive_continuous(eng, clock, warm_reqs,
                          np.zeros(len(warm_reqs)) + clock.now())
        warm = eng.metrics()
        cap_tps = warm["tokens_emitted"] / max(clock.now() - t0, 1e-9)
        traces_warm = warm["traces"]
        eng.reset_metrics(keep_results=False)

        if arrivals is None:
            mean_new = float(np.mean([m for _, m in meas_reqs]))
            rate = load * cap_tps / mean_new
            arr_rng = np.random.RandomState(seed + 1)
            arrivals = np.cumsum(
                arr_rng.exponential(1.0 / rate, size=len(meas_reqs)))
        arr = arrivals + clock.now()
        sub = _drive_continuous(eng, clock, meas_reqs, arr)
        _ttft, _lat, toks = _collect(eng, sub, arr)
        m = eng.metrics()
        # workload index -> emitted tokens: the parity surface (both
        # runs see the same requests at the same arrivals)
        tokens_by_req = {j: eng.results[rid]["tokens"].tolist()
                         for rid, (j, _t) in sub.items()}
        return {
            "label": label,
            "tokens": toks,
            "retraces_after_warmup": m["traces"] - traces_warm,
            "kv_shard_count": m["kv_shard_count"],
            "kv_shard_heads": m["kv_shard_heads"],
            "kv_shard_pool_bytes": m["kv_shard_pool_bytes"],
        }, arrivals, tokens_by_req

    # mp=1 baseline FIRST (the mesh, once initialized, is process-
    # global); then the sharded engine replays the SAME arrivals
    base, arrivals, base_toks = run_mode("mp1")
    init_serving_mesh(mp)
    shard, _, shard_toks = run_mode(f"mp{mp}", arrivals)

    parity_ok = (set(base_toks) == set(shard_toks)
                 and all(base_toks[j] == shard_toks[j]
                         for j in base_toks))
    # per-device residency: each shard holds exactly the dense pool/mp
    pool_full = base["kv_shard_pool_bytes"] * base["kv_shard_count"]
    shard_bytes_ok = (
        shard["kv_shard_count"] == mp
        and shard["kv_shard_pool_bytes"] * mp == pool_full)

    record = {
        "metric": "serving_mesh_tp_parity",
        "value": int(parity_ok),
        "unit": f"1 = exact greedy parity mp={mp} vs mp=1 (same arrivals)",
        "mesh_mp": mp,
        "parity_ok": parity_ok,
        "requests_compared": len(base_toks),
        "retraces_after_warmup": shard["retraces_after_warmup"],
        "retraces_after_warmup_mp1": base["retraces_after_warmup"],
        "kv_shard_count": shard["kv_shard_count"],
        "kv_shard_heads": shard["kv_shard_heads"],
        "kv_shard_pool_bytes": shard["kv_shard_pool_bytes"],
        "kv_pool_bytes_total": pool_full,
        "shard_bytes_ok": shard_bytes_ok,
        # a count drill on forced host devices: the parity/retrace/
        # residency gates are the result, and no rate or latency is
        # written (the arrival schedule still paces on the host clock)
        "devices_forced_host": True,
        "max_seq": smax, "decode_chunk": chunk, "block_tokens": cap_,
        "num_slots": slots, "layers": L, "hidden": E, "heads": H,
        "vocab": V, "requests": n_meas, "offered_load": load,
        "seed": seed, "device": _device_record(dev),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "mesh_serving", record)
    print(json.dumps(record))
    rc = 0
    if not parity_ok:
        print("bench_serving: MESH/SINGLE-DEVICE TOKEN PARITY BROKE",
              file=sys.stderr)
        rc = 1
    if record["retraces_after_warmup"]:
        print("bench_serving: RETRACES AFTER WARMUP on the sharded "
              "engine — block churn leaked into the trace key",
              file=sys.stderr)
        rc = 1
    if not shard_bytes_ok:
        print("bench_serving: PER-SHARD POOL RESIDENCY DOES NOT "
              f"RECONCILE (shard bytes x {mp} != mp=1 pool bytes)",
              file=sys.stderr)
        rc = 1
    return rc


def main_mesh_weights():
    """Weight-sharding A/B on the serving mesh: the SAME paged engine
    with dense (mp=1) weights vs the stacked layer params tensor-
    parallel over an mp-way mesh per generation.STACKED_PARAM_SPECS
    (fused-qkv/f1 column-parallel, out-proj/f2 row-parallel, sharded
    LM head), identical weights and fixed-seed arrivals. Gates: exact
    greedy token parity per request, zero retraces after warmup
    sharded, and the residency identity — the sharded portion of the
    weights holds exactly 1/mp per device, reconciled against the
    mp=1 engine's dense bytes. Lands under "mesh_weights"."""
    jax, dev = _pin_virtual_cpu_mesh()
    import numpy as np

    from paddle_tpu.inference.serving import AdmissionFull, ServingEngine
    from paddle_tpu.parallel import init_serving_mesh

    mp = int(os.environ.get("BENCH_MESH_MP", "2"))
    slots = int(os.environ.get("BENCH_SLOTS", "4"))
    smax = int(os.environ.get("BENCH_SMAX", "256"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "4"))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS", str(6 * slots)))
    load = float(os.environ.get("BENCH_SERVE_LOAD", "1.5"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))
    cap_ = int(os.environ.get("BENCH_PAGED_CAP", "32"))
    if jax.device_count() < mp:
        print(f"bench_serving: --mesh-weights needs >= {mp} devices, "
              f"found {jax.device_count()}", file=sys.stderr)
        return 1

    # the --mesh drill's small fp32 model: H=8 and FF=1024 divide mp, and
    # V=512 is even so the LM head shards too (every STACKED spec plus
    # the head path actually exercises under mp=2)
    fmt, embed, head, (E, H, FF, L, V) = _build_model(
        dims=(256, 8, 1024, 4, 512))
    if H % mp or FF % mp:
        print(f"bench_serving: --mesh-weights mp={mp} does not divide "
              f"num_heads={H} / ffn_dim={FF}", file=sys.stderr)
        return 1

    rng = np.random.RandomState(seed)

    def make(n):
        reqs = []
        for _ in range(n):
            plen = int(rng.randint(6, 25))
            max_new = int(rng.choice([16, 24, 32]))
            reqs.append((rng.randint(1, V, (plen,)).astype("int32"),
                         max_new))
        return reqs

    bucket_reqs = [(rng.randint(1, V, (p,)).astype("int32"), 4)
                   for p in (8, 16, 24)]
    warm_reqs = make(2 * slots)
    meas_reqs = make(n_meas)

    def run_mode(label, arrivals=None):
        clock = VirtualClock()
        eng = ServingEngine(fmt, embed, head, num_slots=slots,
                            max_seq_len=smax, decode_chunk=chunk,
                            prefill_cap=cap_, paged=True,
                            clock=clock.now)
        for prompt, max_new in bucket_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
            eng.run()
        for prompt, max_new in warm_reqs:
            try:
                eng.submit(prompt, max_new_tokens=max_new)
            except AdmissionFull:
                eng.run()
                eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        eng.reset_metrics(keep_results=False)
        t0 = clock.now()
        _drive_continuous(eng, clock, warm_reqs,
                          np.zeros(len(warm_reqs)) + clock.now())
        warm = eng.metrics()
        cap_tps = warm["tokens_emitted"] / max(clock.now() - t0, 1e-9)
        traces_warm = warm["traces"]
        eng.reset_metrics(keep_results=False)

        if arrivals is None:
            mean_new = float(np.mean([m for _, m in meas_reqs]))
            rate = load * cap_tps / mean_new
            arr_rng = np.random.RandomState(seed + 1)
            arrivals = np.cumsum(
                arr_rng.exponential(1.0 / rate, size=len(meas_reqs)))
        arr = arrivals + clock.now()
        sub = _drive_continuous(eng, clock, meas_reqs, arr)
        _ttft, _lat, toks = _collect(eng, sub, arr)
        m = eng.metrics()
        tokens_by_req = {j: eng.results[rid]["tokens"].tolist()
                         for rid, (j, _t) in sub.items()}
        return {
            "label": label,
            "tokens": toks,
            "retraces_after_warmup": m["traces"] - traces_warm,
            "weight_shard_count": m["weight_shard_count"],
            "weight_bytes_per_device": m["weight_bytes_per_device"],
            "weight_bytes_replicated": m["weight_bytes_replicated"],
        }, arrivals, tokens_by_req

    # mp=1 baseline FIRST (the mesh, once initialized, is process-
    # global); then the sharded engine replays the SAME arrivals
    base, arrivals, base_toks = run_mode("mp1")
    init_serving_mesh(mp, num_heads=H, ffn_dim=FF)
    shard, _, shard_toks = run_mode(f"mp{mp}", arrivals)

    parity_ok = (set(base_toks) == set(shard_toks)
                 and all(base_toks[j] == shard_toks[j]
                         for j in base_toks))
    # residency identity: mp=1 holds the dense weights whole, so its
    # per-device bytes ARE the dense total; sharded, the non-replicated
    # portion must hold exactly 1/mp of itself per device —
    # (per_dev - repl) x mp + repl == dense
    dense_bytes = base["weight_bytes_per_device"]
    per_dev = shard["weight_bytes_per_device"]
    repl = shard["weight_bytes_replicated"]
    weight_bytes_ok = (
        shard["weight_shard_count"] == mp
        and base["weight_shard_count"] == 1
        and base["weight_bytes_replicated"] == dense_bytes
        and (per_dev - repl) * mp + repl == dense_bytes
        and per_dev < dense_bytes)

    record = {
        "metric": "serving_mesh_weight_shard",
        "value": round(dense_bytes / max(per_dev, 1), 3),
        "unit": f"x weight bytes/device mp=1 vs mp={mp}",
        "mesh_mp": mp,
        "parity_ok": parity_ok,
        "requests_compared": len(base_toks),
        "retraces_after_warmup": shard["retraces_after_warmup"],
        "retraces_after_warmup_mp1": base["retraces_after_warmup"],
        "weight_shard_count": shard["weight_shard_count"],
        "weight_bytes_per_device": per_dev,
        "weight_bytes_replicated": repl,
        "weight_bytes_dense": dense_bytes,
        "weight_bytes_ok": weight_bytes_ok,
        # a count drill on forced host devices: the parity/retrace/
        # residency gates are the result; no rate or latency is written
        "devices_forced_host": True,
        "max_seq": smax, "decode_chunk": chunk, "block_tokens": cap_,
        "num_slots": slots, "layers": L, "hidden": E, "heads": H,
        "ffn": FF, "vocab": V, "requests": n_meas,
        "offered_load": load, "seed": seed,
        "device": _device_record(dev),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "mesh_weights", record)
    print(json.dumps(record))
    rc = 0
    if not parity_ok:
        print("bench_serving: SHARDED/DENSE-WEIGHT TOKEN PARITY BROKE",
              file=sys.stderr)
        rc = 1
    if record["retraces_after_warmup"]:
        print("bench_serving: RETRACES AFTER WARMUP with sharded "
              "weights — placement leaked into the trace key",
              file=sys.stderr)
        rc = 1
    if not weight_bytes_ok:
        print("bench_serving: WEIGHT RESIDENCY DOES NOT RECONCILE "
              f"((per_device - replicated) x {mp} + replicated != "
              "dense bytes)", file=sys.stderr)
        rc = 1
    return rc


def main_quant():
    """Quantized-serving A/B (ISSUE 20): the SAME flat-budget paged
    engine in three precision flavors at the SAME fixed-seed arrivals —
    fp (baseline), int8 (int8 weights + int8 KV pool), int4 (packed
    int4 weights + int8 KV pool, the end-to-end quantized config).
    Quantization changes logits, so the parity oracle is NEVER fp:
    each flavor's gate is exact greedy token parity between its flat
    [T] and row [B, C] layouts (the layout must stay invisible in
    every flavor). Further gates: the int8 pool (+ scale mirrors)
    holds <= 1/2 the fp pool bytes, the int4 stack <= 1/4 (int8
    <= 1/2) of the fp stacked-weight bytes, the flat i8 Pallas kernel
    REALLY dispatched in the quantized flavors (trace-time spy — the
    gather fallback alone would pass parity silently), and zero
    retraces after warmup everywhere. Lands under "quantized"."""
    jax, dev = _require_tpu()
    import numpy as np

    import paddle_tpu.ops.pallas.decode_attention as da
    from paddle_tpu.inference.serving import AdmissionFull, ServingEngine

    slots = int(os.environ.get("BENCH_SLOTS", "8"))
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "4"))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS", str(4 * slots)))
    load = float(os.environ.get("BENCH_SERVE_LOAD", "1.5"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))
    # prefill_cap IS the pool block size: 32 satisfies the flat i8
    # kernel's int8 sublane minimum (Bt % 32 == 0)
    cap_ = int(os.environ.get("BENCH_PAGED_CAP", "32"))

    # every int4-contracted axis (E=768, nh*hd=768, FF=3072) is even, so
    # all three flavors build
    fmt, embed, head, (E, H, FF, L, V) = _build_model()

    rng = np.random.RandomState(seed)

    def make(n):
        reqs = []
        for _ in range(n):
            plen = int(rng.randint(6, 25))
            max_new = int(rng.choice([16, 24, 32]))
            reqs.append((rng.randint(1, V, (plen,)).astype("int32"),
                         max_new))
        return reqs

    bucket_reqs = [(rng.randint(1, V, (p,)).astype("int32"), 4)
                   for p in (8, 16, 24)]
    warm_reqs = make(2 * slots)
    meas_reqs = make(n_meas)

    i8_kernel_calls = {"n": 0}
    _orig_i8 = da.decode_attention_paged_flat_i8

    def _spy_i8(*a, **k):
        i8_kernel_calls["n"] += 1
        return _orig_i8(*a, **k)
    da.decode_attention_paged_flat_i8 = _spy_i8

    def run_mode(label, flat, arrivals=None, **quant_kw):
        import paddle_tpu as paddle
        clock = VirtualClock()
        paddle.seed(0)
        eng = ServingEngine(fmt, embed, head, num_slots=slots,
                            max_seq_len=smax, decode_chunk=chunk,
                            prefill_cap=cap_, paged=True,
                            flat_budget=flat, clock=clock.now,
                            **quant_kw)
        for prompt, max_new in bucket_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
            eng.run()
        for prompt, max_new in warm_reqs:
            try:
                eng.submit(prompt, max_new_tokens=max_new)
            except AdmissionFull:
                eng.run()
                eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        eng.reset_metrics(keep_results=False)
        t0 = clock.now()
        _drive_continuous(eng, clock, warm_reqs,
                          np.zeros(len(warm_reqs)) + clock.now())
        warm = eng.metrics()
        cap_tps = warm["tokens_emitted"] / max(clock.now() - t0, 1e-9)
        eng.reset_metrics(keep_results=False)

        if arrivals is None:
            mean_new = float(np.mean([m for _, m in meas_reqs]))
            rate = load * cap_tps / mean_new
            arr_rng = np.random.RandomState(seed + 1)
            arrivals = np.cumsum(
                arr_rng.exponential(1.0 / rate, size=len(meas_reqs)))
        arr = arrivals + clock.now()
        t_start = clock.now()
        sub = _drive_continuous(eng, clock, meas_reqs, arr)
        elapsed = clock.now() - t_start
        _ttft, _lat, toks = _collect(eng, sub, arr)
        m = eng.metrics()
        tokens_by_req = {j: eng.results[rid]["tokens"].tolist()
                         for rid, (j, _t) in sub.items()}

        # retrace gate, DETERMINISTIC replay: arrival interleaving
        # under VirtualClock is wall-time dependent, so the flat
        # ladder's pow-2 widths can legitimately differ between two
        # clock-driven passes — the zero-retrace contract is
        # "identical churn retraces nothing" (the tier-1 idiom), so
        # gate on a batch-submitted stream replayed exactly
        def _batch():
            for prompt, max_new in meas_reqs:
                try:
                    eng.submit(prompt, max_new_tokens=max_new)
                except AdmissionFull:
                    eng.run()
                    eng.submit(prompt, max_new_tokens=max_new)
            eng.run()

        _batch()
        traces_batch = eng.metrics()["traces"]
        _batch()
        retraces = eng.metrics()["traces"] - traces_batch

        pool_bytes = int(eng._caches["kv"].nbytes)
        if "sc" in eng._caches:
            pool_bytes += int(eng._caches["sc"].nbytes)
        stack_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                          for a in eng.dec._stacked().values())
        return {
            "label": label,
            "tokens": toks,
            "tokens_per_sec": round(toks / max(elapsed, 1e-9), 2),
            "retraces_after_warmup": retraces,
            "pool_bytes": pool_bytes,
            "stacked_weight_bytes": stack_bytes,
        }, arrivals, tokens_by_req

    FLAVORS = (("fp", {}),
               ("int8", dict(weight_quant="int8", kv_quant="int8")),
               ("int4", dict(weight_quant="int4", kv_quant="int8")))
    try:
        runs = {}
        parity = {}
        arrivals = None
        for name, kw in FLAVORS:
            before = i8_kernel_calls["n"]
            rec_f, arrivals, toks_f = run_mode(
                f"{name}-flat", True, arrivals, **kw)
            rec_f["i8_kernel_dispatched"] = i8_kernel_calls["n"] > before
            rec_r, _, toks_r = run_mode(f"{name}-row", False, arrivals,
                                        **kw)
            parity[name] = (set(toks_f) == set(toks_r)
                            and all(toks_f[j] == toks_r[j]
                                    for j in toks_f))
            runs[name] = {"flat": rec_f, "row": rec_r}
    finally:
        da.decode_attention_paged_flat_i8 = _orig_i8

    fp_pool = runs["fp"]["flat"]["pool_bytes"]
    fp_stack = runs["fp"]["flat"]["stacked_weight_bytes"]
    pool_bytes_ok = (runs["int8"]["flat"]["pool_bytes"] <= fp_pool / 2
                     and runs["int4"]["flat"]["pool_bytes"]
                     <= fp_pool / 2)
    weight_bytes_ok = (
        runs["int8"]["flat"]["stacked_weight_bytes"] <= fp_stack / 2
        and runs["int4"]["flat"]["stacked_weight_bytes"] <= fp_stack / 4)
    kernel_ok = (runs["int8"]["flat"]["i8_kernel_dispatched"]
                 and runs["int4"]["flat"]["i8_kernel_dispatched"])
    retraces = {f"{n}-{side}": runs[n][side]["retraces_after_warmup"]
                for n in runs for side in ("flat", "row")}
    retrace_ok = not any(runs[n][side]["retraces_after_warmup"]
                         for n in runs for side in ("flat", "row"))
    parity_ok = all(parity.values())

    record = {
        "metric": "serving_quantized",
        "value": round(fp_stack
                       / max(runs["int4"]["flat"]
                             ["stacked_weight_bytes"], 1), 3),
        "unit": "x stacked weight bytes fp vs int4",
        "parity_ok": parity_ok,
        "parity_by_flavor": parity,
        "requests_compared": n_meas,
        "i8_kernel_dispatched": kernel_ok,
        "pool_bytes_fp": fp_pool,
        "pool_bytes_int8": runs["int8"]["flat"]["pool_bytes"],
        "pool_bytes_ok": pool_bytes_ok,
        "weight_bytes_fp": fp_stack,
        "weight_bytes_int8": runs["int8"]["flat"]
                                 ["stacked_weight_bytes"],
        "weight_bytes_int4": runs["int4"]["flat"]
                                 ["stacked_weight_bytes"],
        "weight_bytes_ok": weight_bytes_ok,
        "retraces_after_warmup": max(retraces.values()),
        "retrace_ok": retrace_ok,
        "tokens_per_sec": {n: runs[n]["flat"]["tokens_per_sec"]
                           for n in runs},
        "max_seq": smax, "decode_chunk": chunk, "block_tokens": cap_,
        "num_slots": slots, "layers": L, "hidden": E, "heads": H,
        "ffn": FF, "vocab": V, "requests": n_meas,
        "offered_load": load, "seed": seed, "device": _device_record(dev),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "quantized", record)
    print(json.dumps(record))
    rc = 0
    if not parity_ok:
        print("bench_serving: FLAT/ROW TOKEN PARITY BROKE in "
              f"{[n for n, ok in parity.items() if not ok]}",
              file=sys.stderr)
        rc = 1
    if not kernel_ok:
        print("bench_serving: the flat i8 Pallas kernel NEVER "
              "dispatched — the quantized flavors ran the gather "
              "fallback", file=sys.stderr)
        rc = 1
    if not pool_bytes_ok:
        print("bench_serving: INT8 POOL BYTES NOT HALVED "
              f"(fp {fp_pool}, int8 "
              f"{runs['int8']['flat']['pool_bytes']})", file=sys.stderr)
        rc = 1
    if not weight_bytes_ok:
        print("bench_serving: QUANTIZED WEIGHT BYTES OFF "
              f"(fp {fp_stack}, int8 "
              f"{runs['int8']['flat']['stacked_weight_bytes']}, int4 "
              f"{runs['int4']['flat']['stacked_weight_bytes']})",
              file=sys.stderr)
        rc = 1
    if not retrace_ok:
        print(f"bench_serving: RETRACES AFTER WARMUP: {retraces}",
              file=sys.stderr)
        rc = 1
    return rc


def _make_longprompt_workload(rng, n, v, smax, long_frac):
    """The TTFT-hostage regime: a Poisson mix where most requests carry
    LONG prompts (document/context-stuffing traffic) next to short
    interactive ones, all with short-to-medium generations — under
    phase prefill one long admission stalls the whole decode gang and
    the short requests' TTFT p99 blows out."""
    import numpy as np
    reqs = []
    for _ in range(n):
        if rng.uniform() < long_frac:
            plen = int(rng.randint(96, 161))
        else:
            plen = int(rng.randint(8, 25))
        max_new = int(rng.choice([8, 16, 24]))
        prompt = rng.randint(1, v, (plen,)).astype("int32")
        reqs.append((prompt, min(max_new, smax - plen)))
    return reqs


def main_chunked():
    """Token-budget (chunked prefill) overload A/B: the chunked engine
    (default token_budget) vs the legacy phase-prefill engine
    (token_budget=0), same compiled shapes, same fixed-seed long-prompt
    Poisson workload at 2x offered load and the SAME arrivals (rate
    from the PHASE engine's measured capacity). TTFT percentiles come
    straight from engine metrics() — the engine owns them now — and
    the headline value is the chunked side's p99/p50 flatness ratio.
    Also runs an exact greedy chunked-vs-phase token-parity check and
    asserts the zero-retrace contract on both sides. Lands under
    "chunked_prefill" in BENCH_serving.json."""
    jax, dev = _require_tpu()
    import numpy as np

    from paddle_tpu.inference.serving import ServingEngine

    slots = int(os.environ.get("BENCH_SLOTS", "8"))
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "4"))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS", str(6 * slots)))
    load = float(os.environ.get("BENCH_SERVE_LOAD", "2.0"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))
    long_frac = float(os.environ.get("BENCH_CHUNKED_LONG", "0.6"))
    # a serving-scale budget (Sarathi budgets are hundreds of tokens):
    # C = budget/B columns per row, so 64/slot lets a whole classic
    # prompt (and a 64-token chunk of a long one) land per dispatch.
    # The ENGINE default stays B x decode_chunk — right for
    # latency-lean deployments; a bench at overload wants throughput.
    tb_env = os.environ.get("BENCH_TOKEN_BUDGET")
    token_budget = int(tb_env) if tb_env else 64 * slots

    fmt, embed, head, (E, H, FF, L, V) = _build_model()

    rng = np.random.RandomState(seed)
    # solo admissions covering every pow-2 prefill bucket the phase
    # engine's bulk admission can hit (8..256); the chunked engine's
    # budget core is shape-invariant but warms on the same stream
    bucket_reqs = [(rng.randint(1, V, (p,)).astype("int32"), 4)
                   for p in (4, 8, 16, 32, 64, 128, 160)]
    warm_reqs = _make_longprompt_workload(rng, 2 * slots, V, smax,
                                          long_frac)
    meas_reqs = _make_longprompt_workload(rng, n_meas, V, smax,
                                          long_frac)
    # the THROUGHPUT and FLATNESS gates run on the CLASSIC workload
    # shape (the tentpole's "tokens/s within 5% of the phase baseline
    # on the classic workload"; the 2-3x p99/p50 complaint in the
    # motivation IS the classic record's) at 2x the classic record's
    # offered load (2 x 1.5 = 3.0) — chunking must not tax the steady
    # mixed-length flow AND must keep its TTFT tail flat where phase
    # admission stalls spike it
    classic_load = float(os.environ.get("BENCH_CHUNKED_CLASSIC_LOAD",
                                        "3.0"))
    classic_reqs = _make_workload(rng, n_meas, V, min(smax, 128))

    def run_mode(tb, reqs, ld, arrivals=None):
        clock = VirtualClock()
        eng = ServingEngine(fmt, embed, head, num_slots=slots,
                            max_seq_len=smax, decode_chunk=chunk,
                            clock=clock.now, token_budget=tb)
        for prompt, max_new in bucket_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
            eng.run()
        for prompt, max_new in warm_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        eng.reset_metrics(keep_results=False)
        t0 = clock.now()
        for prompt, max_new in warm_reqs:
            eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
        warm = eng.metrics()
        cap = warm["tokens_emitted"] / max(clock.now() - t0, 1e-9)
        traces_warm = warm["traces"]
        eng.reset_metrics(keep_results=False)

        if arrivals is None:
            mean_new = float(np.mean([m for _, m in reqs]))
            rate = ld * cap / mean_new
            arr_rng = np.random.RandomState(seed + 1)
            arrivals = np.cumsum(
                arr_rng.exponential(1.0 / rate, size=len(reqs)))
        arr = arrivals + clock.now()
        t_start = clock.now()
        _drive_continuous(eng, clock, reqs, arr)
        elapsed = clock.now() - t_start
        m = eng.metrics()
        # TTFT/latency straight from the engine (satellite: the bench
        # no longer computes percentiles out-of-band) — the driver
        # submits each request the moment it is due, so submit-based
        # engine TTFT matches the arrival-based view
        return {
            "scheduler": "chunked" if tb != 0 else "phase",
            "token_budget": eng.token_budget,
            "tokens": m["tokens_emitted"],
            "tokens_per_sec": round(m["tokens_emitted"]
                                    / max(elapsed, 1e-9), 2),
            "elapsed_s": round(elapsed, 3),
            "capacity_tokens_per_sec": round(cap, 2),
            "retraces_after_warmup": m["traces"] - traces_warm,
            "ttft_p50_ms": round(1e3 * m["ttft_p50_s"], 1),
            "ttft_p90_ms": round(1e3 * m["ttft_p90_s"], 1),
            "ttft_p99_ms": round(1e3 * m["ttft_p99_s"], 1),
            "ttft_p99_over_p50": round(m["ttft_p99_s"]
                                       / max(m["ttft_p50_s"], 1e-9), 3),
            "latency_p50_ms": round(1e3 * m["latency_p50_s"], 1),
            "latency_p99_ms": round(1e3 * m["latency_p99_s"], 1),
            "budget_steps": m["budget_steps"],
            "budget_utilization": m["budget_utilization"],
            "budget_prefill_tokens": m["budget_prefill_tokens"],
        }, arrivals

    # long-prompt overload half (the TTFT-flatness story), then the
    # classic-workload half (the throughput-parity gate), each with
    # SAME arrivals across the two schedulers
    phase, arrivals = run_mode(0, meas_reqs, load)
    chunked, _ = run_mode(token_budget, meas_reqs, load, arrivals)
    phase_cl, arr_cl = run_mode(0, classic_reqs, classic_load)
    chunk_cl, _ = run_mode(token_budget, classic_reqs, classic_load,
                           arr_cl)

    # exact greedy parity at equal shape (the scheduler-invisibility
    # token contract)
    par_reqs = _make_longprompt_workload(rng, 2 * slots, V, smax,
                                         long_frac)

    def parity_run(tb, flat=False):
        eng = ServingEngine(fmt, embed, head, num_slots=slots,
                            max_seq_len=smax, decode_chunk=chunk,
                            token_budget=tb, flat_budget=flat)
        rids = [eng.submit(p, max_new_tokens=m) for p, m in par_reqs]
        eng.run()
        return [eng.results[r]["tokens"].tolist() for r in rids]

    parity_ok = parity_run(token_budget) == parity_run(0)

    # ---- flat-vs-row A/B (ISSUE 13): the token-FLATTENED [T] dispatch
    # against the row-aligned [B, C] block on the long-prompt mix (the
    # (B-1) x C waste workload), SAME arrivals. The flat engine warms
    # by driving the EXACT measured stream once (virtual clock ->
    # deterministic replay -> identical pow-2 ladder buckets), so the
    # zero-retrace gate is meaningful. The count gauge is
    # budget_padding_tokens (wasted computed positions).
    def run_flat_ab(flat, reqs, arrivals):
        clock = VirtualClock()
        eng = ServingEngine(fmt, embed, head, num_slots=slots,
                            max_seq_len=smax, decode_chunk=chunk,
                            clock=clock.now, token_budget=token_budget,
                            flat_budget=flat)
        arr = arrivals + clock.now()
        _drive_continuous(eng, clock, reqs, arr)        # self-warm pass
        traces_warm = eng.metrics()["traces"]
        eng.reset_metrics(keep_results=False)
        arr = arrivals + clock.now()
        t0 = clock.now()
        _drive_continuous(eng, clock, reqs, arr)
        elapsed = clock.now() - t0
        m = eng.metrics()
        return {
            "layout": "flat" if flat else "row",
            "tokens": m["tokens_emitted"],
            "tokens_per_sec": round(m["tokens_emitted"]
                                    / max(elapsed, 1e-9), 2),
            "budget_steps": m["budget_steps"],
            "budget_tokens_used": m["budget_tokens_used"],
            "budget_padding_tokens": m["budget_padding_tokens"],
            "budget_utilization": m["budget_utilization"],
            "ttft_p99_ms": round(1e3 * m["ttft_p99_s"], 1),
            "retraces_after_warmup": m["traces"] - traces_warm,
        }

    flat_ab = run_flat_ab(True, meas_reqs, arrivals)
    row_ab = run_flat_ab(False, meas_reqs, arrivals)
    flat_parity_ok = (parity_run(token_budget, flat=True)
                      == parity_run(token_budget, flat=False))

    record = {
        "metric": "serving_chunked_prefill_ttft_p99_over_p50",
        # headline: TTFT-tail flatness on the long-prompt mix at 2x
        # offered load, chunked vs the phase scheduler at the SAME
        # arrivals. At a SUSTAINED 2-3x overload the p99/p50 ratio is
        # backlog-shaped for ANY scheduler; the <= 1.3 regime needs
        # compute-bound prefill, where one bulk prefill costs tens of
        # decode steps and its victims dominate the tail.
        "value": chunked["ttft_p99_over_p50"],
        "unit": "x (chunked TTFT p99/p50, long-prompt mix at 2x load)",
        "phase_ttft_p99_over_p50": phase["ttft_p99_over_p50"],
        "ttft_p50_ms_chunked": chunked["ttft_p50_ms"],
        "ttft_p90_ms_chunked": chunked["ttft_p90_ms"],
        "ttft_p99_ms_chunked": chunked["ttft_p99_ms"],
        "ttft_p50_ms_phase": phase["ttft_p50_ms"],
        "ttft_p99_ms_phase": phase["ttft_p99_ms"],
        "longprompt_tokens_per_sec_ratio": round(
            chunked["tokens_per_sec"]
            / max(phase["tokens_per_sec"], 1e-9), 3),
        # the throughput-parity gate: the CLASSIC workload at 2x the
        # classic record's offered load (tokens/s within 5% of phase)
        "classic_load": classic_load,
        "tokens_per_sec_chunked": chunk_cl["tokens_per_sec"],
        "tokens_per_sec_phase": phase_cl["tokens_per_sec"],
        "tokens_per_sec_ratio": round(
            chunk_cl["tokens_per_sec"]
            / max(phase_cl["tokens_per_sec"], 1e-9), 3),
        "classic_ttft_p99_over_p50": chunk_cl["ttft_p99_over_p50"],
        "classic_ttft_p99_over_p50_phase":
            phase_cl["ttft_p99_over_p50"],
        "retraces_after_warmup_classic": (
            chunk_cl["retraces_after_warmup"]
            + phase_cl["retraces_after_warmup"]),
        "latency_p50_ms_chunked": chunked["latency_p50_ms"],
        "latency_p50_ms_phase": phase["latency_p50_ms"],
        "token_budget": chunked["token_budget"],
        "budget_steps": chunked["budget_steps"],
        "budget_utilization": chunked["budget_utilization"],
        "budget_prefill_tokens": chunked["budget_prefill_tokens"],
        "parity_ok": parity_ok,
        # flat-vs-row A/B (same arrivals, long-prompt mix): the flat
        # layout collapses wasted positions (padding_ratio ~ a few %);
        # whether that is a time win on the chip is ROADMAP.md S4
        "flat_ab": flat_ab,
        "row_ab": row_ab,
        "flat_padding_ratio": round(
            flat_ab["budget_padding_tokens"]
            / max(row_ab["budget_padding_tokens"], 1), 4),
        "flat_parity_ok": flat_parity_ok,
        "retraces_after_warmup": chunked["retraces_after_warmup"],
        "retraces_after_warmup_phase": phase["retraces_after_warmup"],
        "long_prompt_fraction": long_frac,
        "num_slots": slots, "max_seq": smax, "decode_chunk": chunk,
        "layers": L, "hidden": E, "vocab": V,
        "requests": n_meas, "offered_load": load, "seed": seed,
        "device": _device_record(dev),
        "cache_mode": ("int8" if os.environ.get(
            "PADDLE_TPU_DECODE_INT8_CACHE") == "1" else "fp"),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "chunked_prefill", record)
    print(json.dumps(record))
    rc = 0
    if record["retraces_after_warmup"] or \
            record["retraces_after_warmup_phase"]:
        print("bench_serving: RETRACES AFTER WARMUP under the token-"
              "budget scheduler — the fixed-shape contract is broken",
              file=sys.stderr)
        rc = 1
    if not parity_ok:
        print("bench_serving: CHUNKED/PHASE TOKEN PARITY BROKE",
              file=sys.stderr)
        rc = 1
    if flat_ab["retraces_after_warmup"] or row_ab["retraces_after_warmup"]:
        print("bench_serving: RETRACES AFTER WARMUP in the flat-vs-row "
              "A/B — the ladder/fixed-shape contract is broken",
              file=sys.stderr)
        rc = 1
    if not flat_parity_ok:
        print("bench_serving: FLAT/ROW TOKEN PARITY BROKE",
              file=sys.stderr)
        rc = 1
    return rc


def _drive_cluster(router, reps, clock, reqs, arrivals, kill_at=None):
    """Drive one router-policy run on the shared virtual clock: submit
    at arrival times, pump every alive replica once per loop, harvest
    incrementally. ``kill_at`` = submission index that triggers killing
    the replica holding the most in-flight requests (the drill);
    returns per-request records + the kill report."""
    from paddle_tpu.inference.serving import AdmissionFull
    from paddle_tpu.serving_cluster import NoReplicaError
    from paddle_tpu.serving_cluster.replica import ReplicaError

    recs = {}            # gid -> {idx, toks, t_first, t_done}
    open_gids = set()
    i = 0
    kill = {"replica": None, "t_kill": None, "t_recovered": None,
            "stranded": 0, "orphaned": 0}
    stranded = set()
    while i < len(reqs) or open_gids:
        now = clock.now()
        while i < len(reqs) and arrivals[i] <= now:
            if kill_at is not None and i >= kill_at \
                    and kill["replica"] is None:
                # the drill: kill whoever holds the most in-flight work
                # (a None owner = failover placement in flight — skip)
                owner_of = {g: router.poll(g)["replica"]
                            for g in open_gids}
                load = {}
                for rep_name in owner_of.values():
                    if rep_name is not None:
                        load[rep_name] = load.get(rep_name, 0) + 1
                if load:
                    victim = max(sorted(load), key=lambda n: load[n])
                    stranded = {g for g, n in owner_of.items()
                                if n == victim}
                    router.replicas[victim].kill()
                    kill.update(replica=victim, t_kill=clock.now(),
                                stranded=len(stranded))
            prompt, max_new = reqs[i]
            try:
                gid = router.submit([int(t) for t in prompt],
                                    max_new_tokens=max_new)
            except AdmissionFull:
                break                     # back off, retry next loop
            recs[gid] = {"idx": i, "toks": [], "t_first": None,
                         "t_done": None}
            open_gids.add(gid)
            i += 1
        progressed = False
        for rep in reps:
            if rep.alive:
                try:
                    progressed |= bool(rep.pump())
                except ReplicaError:
                    pass
        router.check_health()
        for gid in list(open_gids):
            try:
                new, done, state = router.harvest(gid)
            except NoReplicaError:
                # failed failover (everything shed/dead at that
                # instant): close the record honestly so the bench
                # reports the orphan instead of dying mid-drill
                kill["orphaned"] += 1
                new, done, state = [], True, "orphaned"
            r = recs[gid]
            if new and r["t_first"] is None:
                r["t_first"] = clock.now()
            r["toks"].extend(new)
            if done:
                r["t_done"] = clock.now()
                open_gids.discard(gid)
                if gid in stranded:
                    stranded.discard(gid)
                    if not stranded and kill["t_recovered"] is None:
                        kill["t_recovered"] = clock.now()
        if not progressed and not open_gids and i < len(reqs):
            clock.skip_to(arrivals[i])
    return recs, kill


def _cluster_trace_block(router):
    """Export + validate the MERGED cluster Perfetto trace for the kill
    run (the observability acceptance gate, same discipline as the
    classic mode's chrome-trace validity check): the trace must parse,
    and the killed request's failover must appear as the SAME trace id
    with spans on two replicas at attempts 1 and 2.
    ``BENCH_CLUSTER_TRACE_PATH`` persists the artifact (default: temp
    file, deleted after validation)."""
    import tempfile

    from paddle_tpu.inference.telemetry import validate_chrome_trace
    from paddle_tpu.serving_cluster import export_cluster_trace

    keep = os.environ.get("BENCH_CLUSTER_TRACE_PATH")
    if keep:
        path = keep
    else:
        fd, path = tempfile.mkstemp(suffix=".json",
                                    prefix="bench_cluster_trace_")
        os.close(fd)
    out = {"valid": False, "events": 0, "failover_trace_ids": 0,
           "path": keep or None}
    try:
        export_cluster_trace(router, path)
        doc = validate_chrome_trace(path)   # raises on bad structure
        evs = doc["traceEvents"]
        out["events"] = len(evs)
        by_trace = {}
        for e in evs:
            args = e.get("args") or {}
            tid = args.get("trace_id")
            if tid is None or e.get("ph") != "X" \
                    or "attempt" not in args or e.get("pid") == 0:
                continue
            by_trace.setdefault(tid, {"attempts": set(), "pids": set()})
            by_trace[tid]["attempts"].add(args["attempt"])
            by_trace[tid]["pids"].add(e["pid"])
        joined = [t for t, j in by_trace.items()
                  if max(j["attempts"]) >= 2 and len(j["pids"]) >= 2]
        decisions = sum(1 for e in evs
                        if e.get("pid") == 0
                        and str(e.get("name", "")).startswith("route["))
        out["failover_trace_ids"] = len(joined)
        out["router_decisions"] = decisions
        out["valid"] = bool(joined) and decisions > 0
    except Exception as e:
        print(f"bench_serving: cluster trace export failed: {e!r}",
              file=sys.stderr)
    finally:
        if not keep:
            try:
                os.remove(path)
            except OSError:
                pass
    return out


def main_cluster():
    """Router-policy A/B + kill drill over N full in-process replicas
    (see the module docstring). Everything runs unthreaded on ONE
    virtual clock, so heartbeats, failover, and TTFT are deterministic
    functions of the fixed seed."""
    jax, dev = _require_tpu()
    import numpy as np

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.serving_cluster import LocalReplica, Router

    n_rep = int(os.environ.get("BENCH_CLUSTER_REPLICAS", "3"))
    slots = int(os.environ.get("BENCH_SLOTS", "4"))
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    cap_ = int(os.environ.get("BENCH_PREFIX_CAP", "64"))
    # 2-block templates against the default pool (slots x smax/cap
    # blocks): ONE replica's pool can hold only part of the template
    # set, which is exactly the regime where placement pays — with the
    # whole set fitting every replica, any policy converges to all-hit
    # and the A/B measures cold-start noise (measured: affinity showed
    # NO gain at tlen=64 / 8-block pools; +0.10 hit rate, +17%
    # tokens/s, -21% TTFT p50 at tlen=128)
    tlen = int(os.environ.get("BENCH_PREFIX_TLEN",
                              "512"))
    n_templates = int(os.environ.get("BENCH_PREFIX_TEMPLATES", "4"))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                str(20 * n_rep)))
    # load 1.0 (at capacity), not the classic mode's 1.5 overload: the
    # affinity win is CACHE LOCALITY, and a sustained backlog makes
    # every policy queue-bound + spill-dominated — measured at 1.2 the
    # gain is noise (~0.02-0.06 hit rate run-to-run); at 1.0 it is
    # stable (+0.20 hit rate, ~+38% tokens/s, -40% TTFT p50)
    load = float(os.environ.get("BENCH_SERVE_LOAD", "1.0"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))
    kill_at = int(os.environ.get("BENCH_CLUSTER_KILL_AT",
                                 str(n_meas // 2)))
    # spill threshold scaled to the per-replica queue the offered load
    # actually builds: the default knob (4) is tuned for interactive
    # latency, but at a sustained backlog it turns affinity into
    # least-loaded-with-a-hash and the A/B measures nothing
    spill = int(os.environ.get("BENCH_CLUSTER_SPILL_DEPTH",
                               str(4 * slots)))
    pool_blocks = 4 * n_templates * max(tlen // cap_, 1)
    new_choices = [8, 12, 16]
    sfx_lo, sfx_hi = 3, min(8, smax - tlen - max(new_choices))

    fmt, embed, head, (E, H, FF, L, V) = _build_model()
    rng = np.random.RandomState(seed)
    templates = [rng.randint(1, V, (tlen,)).astype("int32")
                 for _ in range(n_templates)]
    meas_reqs = _make_shared_workload(rng, n_meas, V, smax, templates,
                                      sfx_lo, sfx_hi, new_choices)

    # warmup uses a THROWAWAY template of the same shape (same bulk /
    # adopt / suffix-scan buckets) that never appears in the workload:
    # every executable compiles before the measured window, but the
    # measured templates stay COLD everywhere — the per-replica
    # hit-rate then measures exactly the placement signal the A/B is
    # about (a shared warmup would publish every template on every
    # replica and hide it)
    warm_template = rng.randint(1, V, (tlen,)).astype("int32")

    # declared SLO objectives for the goodput block (BENCH_SLO_*;
    # unset = no objectives, every finished request counts ok — the
    # block still records the split machinery end to end)
    from paddle_tpu.inference.telemetry import SloPolicy

    def _env_f(name):
        v = os.environ.get(name)
        return float(v) if v not in (None, "") else None
    slo_policy = SloPolicy(ttft_s=_env_f("BENCH_SLO_TTFT_S"),
                           itl_s=_env_f("BENCH_SLO_ITL_S"),
                           e2e_s=_env_f("BENCH_SLO_E2E_S"))

    def build_engine(clock):
        # paged FORCED: live migration (export/import_slot, warmed
        # below) needs the block pool — a leaked PADDLE_SERVING_PAGED=0
        # must not crash the warmup or silently skip the scale drill
        eng = ServingEngine(
            fmt, embed, head, num_slots=slots, max_seq_len=smax,
            prefill_cap=cap_, prefix_cache_blocks=pool_blocks,
            paged=True, clock=clock.now, slo=slo_policy)
        for sfx in (sfx_lo, sfx_lo, sfx_hi):
            p = np.concatenate([warm_template,
                                np.arange(1, sfx + 1, dtype=np.int32)])
            eng.submit(p, max_new_tokens=max(new_choices))
            eng.run()
        # warm the MIGRATION executables too (BlockPool read/write
        # block): one export/import round-trip on the throwaway
        # template, so the scale drill's live migrations are
        # zero-retrace on every replica — spawned ones included
        p = np.concatenate([warm_template,
                            np.arange(1, sfx_lo + 1, dtype=np.int32)])
        rid = eng.submit(p, max_new_tokens=max(new_choices))
        while rid in eng._req_index and not eng._req_index[rid].tokens:
            eng.step()
        rid = eng.import_slot(eng.export_slot(rid))
        eng.run()
        eng.reset_metrics(keep_results=False)
        return eng

    def build_cluster(policy, clock):
        reps = [LocalReplica(f"replica{r}", build_engine(clock),
                             threaded=False, clock=clock.now)
                for r in range(n_rep)]
        # audit_ring pinned explicitly: the bench's merged-trace gate
        # requires router decision events, so an exported
        # PADDLE_ROUTER_AUDIT_RING=0 must not fail a healthy kill drill
        return reps, Router(reps, policy=policy, hb_dead_s=0.05,
                            spill_depth=spill, snap_max_age_s=0.0,
                            clock=clock.now, audit_ring=4096)

    # template id per request (by prefix identity): the concentration
    # metric below needs to know each request's template home
    tmpl_of = {}
    for i, (prompt, _) in enumerate(meas_reqs):
        for t_id, t in enumerate(templates):
            if prompt.size >= tlen and np.array_equal(prompt[:tlen], t):
                tmpl_of[i] = t_id
                break

    def run_policy(policy, arrivals, kill=False):
        clock = VirtualClock()
        reps, router = build_cluster(policy, clock)
        traces0 = [r.engine.metrics()["traces"] for r in reps]
        arr = arrivals + clock.now()
        t0 = clock.now()
        recs, kill_rep = _drive_cluster(
            router, reps, clock, meas_reqs, arr,
            kill_at=kill_at if kill else None)
        elapsed = clock.now() - t0
        toks = sum(len(r["toks"]) for r in recs.values())
        ttft = [r["t_first"] - arr[r["idx"]] for r in recs.values()
                if r["t_first"] is not None]
        hit_rates = [r.engine.metrics()["prefix_hit_rate"]
                     for r in reps]
        hits = sum(r.engine.metrics()["prefix_hits"] for r in reps)
        misses = sum(r.engine.metrics()["prefix_misses"] for r in reps)
        # per-template CONCENTRATION: the share of each template's
        # requests served by its most-used replica, averaged — 1/N for
        # placement-blind routing, ->1.0 when affinity pins templates
        by_tmpl = {}
        for gid, r in recs.items():
            t_id = tmpl_of.get(r["idx"])
            if t_id is None:
                continue
            rep = router.poll(gid)["replica"]
            by_tmpl.setdefault(t_id, []).append(rep)
        conc = [max(v.count(n) for n in set(v)) / len(v)
                for v in by_tmpl.values() if v]
        out = {
            "policy": policy,
            "tokens": toks,
            "tokens_per_sec": round(toks / max(elapsed, 1e-9), 2),
            "elapsed_s": round(elapsed, 3),
            "ttft_p50_ms": round(1e3 * float(np.percentile(ttft, 50)), 1),
            "ttft_p99_ms": round(1e3 * float(np.percentile(ttft, 99)), 1),
            "prefix_hit_rate_overall": round(hits / max(hits + misses,
                                                        1), 4),
            "per_replica_hit_rate": hit_rates,
            "template_concentration": round(float(np.mean(conc)), 4)
            if conc else None,
            "retraces_after_warmup": [
                r.engine.metrics()["traces"] - t
                for r, t in zip(reps, traces0)],
            "failovers": router.failovers_total,
        }
        # SLO/goodput block: per-replica verdicts + the queue/service
        # decomposition percentiles (the autoscaler's signals, recorded
        # per bench run so regressions are diffable)
        def ms(v):
            return None if v is None else round(1e3 * v, 2)
        per_rep = {}
        for r in reps:
            em = r.engine.metrics()
            per_rep[r.name] = {
                "ok": em["slo_ok"],
                "violated_queue": em["slo_violated_queue"],
                "violated_service": em["slo_violated_service"],
                "finished": em["requests_finished"],
                "queue_p50_ms": ms(em["queue_p50_s"]),
                "queue_p99_ms": ms(em["queue_p99_s"]),
                "service_p50_ms": ms(em["service_p50_s"]),
                "service_p99_ms": ms(em["service_p99_s"]),
            }
        done = sum(p["ok"] + p["violated_queue"] + p["violated_service"]
                   for p in per_rep.values())
        out["slo"] = {
            "objectives": slo_policy.objectives(),
            "ok": sum(p["ok"] for p in per_rep.values()),
            "violated_queue": sum(p["violated_queue"]
                                  for p in per_rep.values()),
            "violated_service": sum(p["violated_service"]
                                    for p in per_rep.values()),
            "requests_classified": done,
            # the independent side of the reconciliation gate: every
            # engine-finished request must have received a verdict
            "requests_finished": sum(p["finished"]
                                     for p in per_rep.values()),
            "per_replica": per_rep,
        }
        by_idx = {r["idx"]: r["toks"] for r in recs.values()}
        if kill:
            out["kill"] = {
                "replica": kill_rep["replica"],
                "stranded_requests": kill_rep["stranded"],
                "orphaned_requests": kill_rep["orphaned"],
                "recovery_window_s": (
                    None if kill_rep["t_recovered"] is None
                    or kill_rep["t_kill"] is None
                    else round(kill_rep["t_recovered"]
                               - kill_rep["t_kill"], 3)),
            }
            out["cluster_trace"] = _cluster_trace_block(router)
        return out, by_idx

    arr_rng = np.random.RandomState(seed + 1)
    # arrival rate anchored on a capacity probe of ONE warmed engine
    # times the replica count (building a whole throwaway cluster for
    # this measured only its first replica and wasted the other N-1
    # compile/warmup cycles)
    probe_clock = VirtualClock()
    probe_eng = build_engine(probe_clock)
    t0 = probe_clock.now()
    for prompt, max_new in meas_reqs[: 4 * slots]:
        probe_eng.submit(prompt, max_new_tokens=max_new)
    probe_eng.run()
    cap_tps = (probe_eng.metrics()["tokens_emitted"]
               / max(probe_clock.now() - t0, 1e-9)) * n_rep
    mean_new = float(np.mean([m for _, m in meas_reqs]))
    arrivals = np.cumsum(arr_rng.exponential(
        mean_new / max(load * cap_tps, 1e-9), size=len(meas_reqs)))

    rr, rr_toks = run_policy("round_robin", arrivals)
    aff, aff_toks = run_policy("prefix_affinity", arrivals)
    killed, kill_toks = run_policy("prefix_affinity", arrivals,
                                   kill=True)
    # greedy parity: the kill run must deliver the EXACT tokens the
    # undisturbed affinity run delivered, for every request
    parity_ok = all(kill_toks[i] == aff_toks[i]
                    for i in range(len(meas_reqs)))

    # ----------------- SCALE CHAOS DRILL: 1 -> 3 -> 1 ----------------
    # ONE replica takes the 3-replica-rate arrivals (3x oversubscribed
    # — the scale-up trigger), the autoscaler grows the set to 3, a
    # mid-load graceful drain of the busiest replica live-migrates its
    # streams (rolling-restart flavor; the autoscaler replaces it if
    # load demands), and the tail's empty queues drain the set back to
    # 1. Gates: greedy token parity vs the no-scale 1-replica run at
    # the SAME arrivals, zero dropped/orphaned streams, ZERO prefill
    # recompute across every drain (migrated slots ship their KV — the
    # engines' prefill_tokens_computed sum is measured around each
    # remove_replica call), zero migration aborts, the 1->3->1 shape,
    # and zero retraces on every engine, spawned replicas included.
    from paddle_tpu.inference.serving import AdmissionFull
    from paddle_tpu.serving_cluster import Autoscaler, NoReplicaError
    from paddle_tpu.serving_cluster.replica import ReplicaError

    drain_at = int(os.environ.get("BENCH_CLUSTER_DRAIN_AT",
                                  str((2 * n_meas) // 3)))

    def run_scale(arrivals, elastic):
        clock = VirtualClock()
        reps, engines, traces0 = [], [], []

        def spawn(name):
            rep = LocalReplica(name, build_engine(clock),
                               threaded=False, clock=clock.now)
            reps.append(rep)
            engines.append(rep.engine)
            traces0.append(rep.engine.metrics()["traces"])
            return rep

        router = Router([spawn("replica0")], policy="least_loaded",
                        hb_dead_s=0.05, spill_depth=spill,
                        snap_max_age_s=0.0, clock=clock.now,
                        audit_ring=4096)
        asc = None
        recompute = {"tokens": 0}
        if elastic:
            asc = Autoscaler(router, spawn, min_replicas=1,
                             max_replicas=3, queue_high=1.5,
                             queue_low=0.5, cooldown_s=0.25,
                             hysteresis=2, clock=clock.now)
            orig_remove = router.remove_replica

            def measured_remove(name, migrate=True):
                # the zero-reprefill gate: the drive is single-threaded
                # on one virtual clock, so nothing else can move the
                # engines' prefill counters during the synchronous
                # drain — any delta IS migration-induced recompute
                pf0 = sum(e.metrics()["prefill_tokens_computed"]
                          for e in engines)
                out = orig_remove(name, migrate=migrate)
                recompute["tokens"] += sum(
                    e.metrics()["prefill_tokens_computed"]
                    for e in engines) - pf0
                return out

            router.remove_replica = measured_remove
        recs = {}
        open_gids = set()
        i = 0
        max_alive = 1
        drained = False
        orphaned = 0
        mid_drain = {"replica": None, "migrated": 0}
        arr = arrivals + clock.now()
        t0 = clock.now()
        while i < len(meas_reqs) or open_gids:
            now = clock.now()
            while i < len(meas_reqs) and arr[i] <= now:
                if elastic and not drained and i >= drain_at \
                        and len(router.placeable_names()) >= 2:
                    # rolling-restart: gracefully drain whoever holds
                    # the most in-flight work — every stream must
                    # LIVE-MIGRATE, none may drop
                    owner_of = {g: router.poll(g)["replica"]
                                for g in open_gids}
                    loadc = {}
                    for rep_name in owner_of.values():
                        if rep_name in router.placeable_names():
                            loadc[rep_name] = loadc.get(rep_name, 0) + 1
                    if loadc:
                        victim = max(sorted(loadc),
                                     key=lambda n: loadc[n])
                        m0 = router.migrations_total
                        router.remove_replica(victim, migrate=True)
                        mid_drain.update(
                            replica=victim,
                            migrated=router.migrations_total - m0)
                        drained = True
                prompt, max_new = meas_reqs[i]
                try:
                    gid = router.submit([int(t) for t in prompt],
                                        max_new_tokens=max_new)
                except AdmissionFull:
                    break
                recs[gid] = {"idx": i, "toks": [], "state": None}
                open_gids.add(gid)
                i += 1
            progressed = False
            for rep in list(reps):
                if rep.alive:
                    try:
                        progressed |= bool(rep.pump())
                    except ReplicaError:
                        pass
            router.check_health()
            if asc is not None:
                asc.tick()
                max_alive = max(max_alive,
                                len(router.placeable_names()))
            for gid in list(open_gids):
                try:
                    new, done, state = router.harvest(gid)
                except NoReplicaError:
                    orphaned += 1
                    new, done, state = [], True, "orphaned"
                recs[gid]["toks"].extend(new)
                if done:
                    recs[gid]["state"] = state
                    open_gids.discard(gid)
            if not progressed and not open_gids and i < len(meas_reqs):
                clock.skip_to(arr[i])
        # tail: the backlog is gone, the low watermark holds — tick
        # through cooldowns until the set is back at the floor
        guard = 0
        while asc is not None and guard < 64 \
                and len(router.placeable_names()) > 1:
            clock.skip_to(clock.now() + asc.cooldown_s + 0.01)
            asc.tick()
            guard += 1
        elapsed = clock.now() - t0
        toks = sum(len(r["toks"]) for r in recs.values())
        by_idx = {r["idx"]: r["toks"] for r in recs.values()}
        return {
            "replicas_spawned": len(reps),
            "max_alive": max_alive,
            "final_alive": len(router.placeable_names()),
            "tokens": toks,
            "tokens_per_sec": round(toks / max(elapsed, 1e-9), 2),
            "elapsed_s": round(elapsed, 3),
            "migrations": router.migrations_total,
            "migration_aborts": router.migration_aborts_total,
            "mid_drain": mid_drain,
            "scale_events": dict(router.scale_events),
            "failovers": router.failovers_total,
            "orphaned": orphaned,
            "unfinished": sum(1 for r in recs.values()
                              if r["state"] != "finished"),
            "submitted": len(recs),
            "prefill_recompute_tokens": recompute["tokens"],
            # whole-run conservation: every admitted prompt token is
            # either computed or prefix-adopted EXACTLY once across the
            # cluster — a migration that replays prefill (even via
            # later pumps, outside the per-drain delta window above)
            # inflates this past the submitted prompt tokens
            "prefill_tokens_accounted": sum(
                e.metrics()["prefill_tokens_computed"]
                + e.metrics()["prefill_tokens_saved"]
                for e in engines),
            "retraces_after_warmup": [
                e.metrics()["traces"] - t
                for e, t in zip(engines, traces0)],
        }, by_idx

    scale_arr_rng = np.random.RandomState(seed + 2)
    scale_arrivals = np.cumsum(scale_arr_rng.exponential(
        mean_new / max(load * cap_tps, 1e-9), size=len(meas_reqs)))
    scale_base, scale_base_toks = run_scale(scale_arrivals,
                                            elastic=False)
    scale_drill, scale_toks = run_scale(scale_arrivals, elastic=True)
    scale_parity = all(scale_toks.get(i) == scale_base_toks.get(i)
                       for i in range(len(meas_reqs)))

    record = {
        "metric": "cluster_prefix_affinity_hit_rate",
        "value": aff["prefix_hit_rate_overall"],
        "unit": "prefix hit rate (vs round_robin "
                f"{rr['prefix_hit_rate_overall']})",
        "replicas": n_rep, "slots_per_replica": slots,
        "spill_depth": spill,
        "max_seq": smax, "prefill_cap": cap_,
        "templates": n_templates, "template_tokens": tlen,
        "requests": n_meas, "offered_load": load, "seed": seed,
        "round_robin": rr,
        "prefix_affinity": aff,
        "kill_drill": killed,
        "kill_token_parity": parity_ok,
        "scale_drill": scale_drill,
        "scale_baseline": scale_base,
        "scale_token_parity": scale_parity,
        # the goodput block the autoscaling item consumes (the kill
        # run's: it includes the failover's queue/service impact)
        "slo": killed["slo"],
        "affinity_hit_rate_gain": round(
            aff["prefix_hit_rate_overall"]
            - rr["prefix_hit_rate_overall"], 4),
        "layers": L, "hidden": E, "vocab": V,
        "device": _device_record(dev),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "cluster", record)
    print(json.dumps(record))
    rc = 0
    if any(rr["retraces_after_warmup"]) or \
            any(aff["retraces_after_warmup"]):
        print("bench_serving: RETRACES AFTER WARMUP on a replica — the "
              "router must be pure host code", file=sys.stderr)
        rc = 1
    if not parity_ok:
        print("bench_serving: KILL-DRILL TOKEN PARITY BROKE — failover "
              "replay is not greedy-identical", file=sys.stderr)
        rc = 1
    if killed["failovers"] == 0 or killed["kill"]["replica"] is None:
        print("bench_serving: the kill drill never killed/failed-over "
              "(workload too short for BENCH_CLUSTER_KILL_AT?)",
              file=sys.stderr)
        rc = 1
    if killed["kill"]["orphaned_requests"]:
        print("bench_serving: KILL DRILL ORPHANED "
              f"{killed['kill']['orphaned_requests']} requests — "
              "failover found no live replica to re-place them on",
              file=sys.stderr)
        rc = 1
    if not killed["cluster_trace"]["valid"]:
        print("bench_serving: MERGED CLUSTER TRACE INVALID — the kill "
              "drill must yield one validated Perfetto trace joining "
              "the failed-over request across two replicas "
              f"({killed['cluster_trace']})", file=sys.stderr)
        rc = 1
    slo_rec = killed["slo"]
    if (slo_rec["ok"] + slo_rec["violated_queue"]
            + slo_rec["violated_service"]) != slo_rec[
                "requests_finished"]:
        print("bench_serving: SLO RECONCILIATION BROKE in the cluster "
              f"record: {slo_rec['requests_classified']} classified "
              f"!= {slo_rec['requests_finished']} engine-finished: "
              f"{slo_rec}", file=sys.stderr)
        rc = 1
    # ---- scale-drill gates (the elastic acceptance criteria) ----
    sd = scale_drill
    if not scale_parity:
        print("bench_serving: SCALE-DRILL TOKEN PARITY BROKE — a "
              "migrated stream is not greedy-identical to the no-scale "
              "run", file=sys.stderr)
        rc = 1
    if sd["orphaned"] or sd["unfinished"] \
            or sd["submitted"] != len(meas_reqs):
        print(f"bench_serving: SCALE DRILL DROPPED STREAMS — "
              f"submitted={sd['submitted']}/{len(meas_reqs)}, "
              f"unfinished={sd['unfinished']}, "
              f"orphaned={sd['orphaned']}", file=sys.stderr)
        rc = 1
    if sd["migrations"] == 0 or sd["mid_drain"]["replica"] is None:
        print("bench_serving: the scale drill never LIVE-MIGRATED a "
              "stream (mid-load drain found no victim? tune "
              "BENCH_CLUSTER_DRAIN_AT)", file=sys.stderr)
        rc = 1
    if sd["migration_aborts"]:
        print(f"bench_serving: {sd['migration_aborts']} migrations "
              "ABORTED to failover during the scale drill",
              file=sys.stderr)
        rc = 1
    if sd["prefill_recompute_tokens"]:
        print("bench_serving: migrated slots RECOMPUTED "
              f"{sd['prefill_recompute_tokens']} prefill tokens — "
              "migration must ship KV, not replay prompts",
              file=sys.stderr)
        rc = 1
    # the delta window above only sees SYNCHRONOUS recompute inside
    # remove_replica; this conservation check catches a migration that
    # replays prefill during later pumps (e.g. pf_left restored as the
    # full prompt): every submitted prompt token must be computed or
    # prefix-adopted exactly once cluster-wide (failovers re-prefill
    # legitimately, so the drill requires zero of them first)
    expected_prefill = sum(int(p.size) for p, _ in meas_reqs)
    if sd["failovers"] \
            or sd["prefill_tokens_accounted"] != expected_prefill:
        print("bench_serving: scale-drill prefill accounting broke — "
              f"computed+saved = {sd['prefill_tokens_accounted']} vs "
              f"{expected_prefill} submitted prompt tokens "
              f"(failovers={sd['failovers']}); migration replayed "
              "prefill work", file=sys.stderr)
        rc = 1
    if sd["max_alive"] < 3 or sd["final_alive"] != 1:
        print(f"bench_serving: scale shape broke — expected 1->3->1, "
              f"got max {sd['max_alive']}, final {sd['final_alive']}",
              file=sys.stderr)
        rc = 1
    if any(sd["retraces_after_warmup"]):
        print("bench_serving: RETRACES AFTER WARMUP during the scale "
              f"drill: {sd['retraces_after_warmup']} — migration and "
              "spawned replicas must reuse warm executables",
              file=sys.stderr)
        rc = 1
    return rc


def main_gray():
    """The GRAY-FAILURE chaos drill (see the module docstring): one
    replica goes slow-but-alive mid-bench — heartbeat fresh, work
    crawling — and the router's defense stack (health scoring,
    circuit breaker, hedged dispatch) must bound the TTFT tail
    without ever declaring the replica dead, then hand the traffic
    back once the slowness lifts."""
    jax, dev = _require_tpu()
    import numpy as np

    from paddle_tpu.inference.serving import AdmissionFull, ServingEngine
    from paddle_tpu.serving_cluster import NoReplicaError, Router
    from paddle_tpu.serving_cluster.replica import LocalReplica, ReplicaError

    n_rep = 3
    slots = int(os.environ.get("BENCH_SLOTS", "4"))
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    cap_ = int(os.environ.get("BENCH_PREFIX_CAP", "64"))
    tlen = int(os.environ.get("BENCH_PREFIX_TLEN",
                              "512"))
    n_templates = 4
    n_meas = int(os.environ.get("BENCH_GRAY_REQUESTS", str(16 * n_rep)))
    # load WELL below the PROBED capacity: gray defense is a
    # tail-latency story and a standing backlog buries the victim's
    # slowness inside queueing noise. The probe measures a bare
    # engine.run loop; the cluster drive adds per-iteration router
    # work (snapshots on every submit, a harvest per open stream), so
    # its real capacity is ~1/3 of probed x n_rep — 0.1 here is ~1/3
    # of true capacity (measured: 0.3 ran the loop at saturation and
    # the healthy p99 matched the injected run's)
    load = float(os.environ.get("BENCH_GRAY_LOAD", "0.1"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))
    # the victim steps only every Nth pump: at a quiet-cluster loop
    # pace (~0.2ms/iteration) 200 skipped pumps ≈ tens of ms per
    # victim step ≈ 10-20x its healthy step time — and the skipped
    # pumps are near-free, so the slowness never stalls the shared
    # single-threaded drive loop the way a real sleep would
    slow_factor = int(os.environ.get("BENCH_GRAY_SLOW_FACTOR", "400"))
    slow_at = int(os.environ.get("BENCH_GRAY_SLOW_AT", str(n_meas // 3)))
    # default: the slowness lifts once the whole measured stream has
    # been submitted and drained (the undefended run must pay the
    # FULL crawl price — lifting mid-window quietly rescues it);
    # BENCH_GRAY_LIFT_AT below n_meas lifts at that submission index
    lift_at = int(os.environ.get("BENCH_GRAY_LIFT_AT", str(n_meas)))
    # half-open cooldown: each half-open probe mid-window sacrifices
    # a real request to the still-slow victim (the canary cost of
    # breaker probing), so the cooldown bounds that to ~1 per window;
    # the post-lift recovery phase skip_to()s across it, so a long
    # cooldown costs no real time there
    cooldown = 1.0
    spill = int(os.environ.get("BENCH_CLUSTER_SPILL_DEPTH",
                               str(4 * slots)))
    pool_blocks = 4 * n_templates * max(tlen // cap_, 1)
    new_choices = [8, 12, 16]
    sfx_lo, sfx_hi = 3, min(8, smax - tlen - max(new_choices))

    fmt, embed, head, (E, H, FF, L, V) = _build_model()
    rng = np.random.RandomState(seed)
    templates = [rng.randint(1, V, (tlen,)).astype("int32")
                 for _ in range(n_templates)]
    meas_reqs = _make_shared_workload(rng, n_meas, V, smax, templates,
                                      sfx_lo, sfx_hi, new_choices)
    warm_template = rng.randint(1, V, (tlen,)).astype("int32")

    def build_engine(clock):
        eng = ServingEngine(
            fmt, embed, head, num_slots=slots, max_seq_len=smax,
            prefill_cap=cap_, prefix_cache_blocks=pool_blocks,
            paged=True, clock=clock.now)
        for sfx in (sfx_lo, sfx_lo, sfx_hi):
            p = np.concatenate([warm_template,
                                np.arange(1, sfx + 1, dtype=np.int32)])
            eng.submit(p, max_new_tokens=max(new_choices))
            eng.run()
        eng.reset_metrics(keep_results=False)
        return eng

    class SlowReplica(LocalReplica):
        """The gray-failure lever: while ``slow_factor`` > 1, only
        every slow_factor-th pump() actually steps the engine — but
        the heartbeat refreshes on EVERY call, so the replica keeps
        LOOKING alive. Deterministic slow-but-alive, the failure mode
        the heartbeat sweep cannot see and the breaker must.
        ``steps_done`` counts REAL engine steps, so the driver can
        tell a skipped (gray) pump from actual progress and advance
        the virtual clock across the crawl instead of spinning."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.slow_factor = 1
            self.steps_done = 0
            self._pumps = 0

        def pump(self):
            self._pumps += 1
            self._check_alive()
            if self.slow_factor > 1 and self._pumps % self.slow_factor:
                self._hb = self._clock()
                return 0
            with self._lock:
                work = self.engine.has_work
                out = self.engine.step() if work else 0
            if work:
                self.steps_done += 1
            self._hb = self._clock()
            return out

    def run_gray(slow, defense):
        clock = VirtualClock()
        reps = [SlowReplica(f"replica{r}", build_engine(clock),
                            threaded=False, clock=clock.now)
                for r in range(n_rep)]
        # knobs pinned explicitly (not env defaults): an exported
        # PADDLE_ROUTER_HEDGE_QUANTILE=0 must not silently disarm the
        # drill; the no-defense arm disables by unreachable thresholds
        # instead of new code paths, so both arms run the same router
        kw = (dict(suspect_ratio=3.0, breaker_ratio=6.0,
                   breaker_errs=3, breaker_probes=1,
                   hedge_quantile=95.0, hedge_margin=2.0,
                   hedge_min_s=0.02, retry_rate=8.0, retry_burst=16)
              if defense else
              dict(suspect_ratio=1e9, breaker_ratio=1e9,
                   breaker_errs=10 ** 9, hedge_quantile=0.0))
        # round_robin ON PURPOSE: queue-aware policies (least_loaded
        # at fresh snapshots) quietly route around a slow replica's
        # standing queue, which would mask the stack under test —
        # queue-blind rotation keeps feeding the victim, so the
        # breaker/hedge layer is the ONLY defense in the A/B
        router = Router(reps, policy="round_robin", hb_dead_s=0.05,
                        spill_depth=spill, snap_max_age_s=0.0,
                        clock=clock.now, audit_ring=4096,
                        breaker_cooldown_s=cooldown, **kw)
        traces0 = [r.engine.metrics()["traces"] for r in reps]
        arr = arrivals + clock.now()
        t0 = clock.now()
        recs = {}
        open_gids = set()
        i = 0
        orphaned = 0
        gray = {"victim": None, "t_slow": None, "t_lift": None}
        victim_rep = None
        while i < len(meas_reqs) or open_gids:
            now = clock.now()
            while i < len(meas_reqs) and arr[i] <= now:
                if slow and victim_rep is None and i >= slow_at:
                    # inject: whoever holds the most in-flight work
                    # goes 20x slow (in-flight streams are what hedges
                    # must rescue); deterministic fallback if the
                    # instant happens to be idle
                    owner_of = {g: router.poll(g)["replica"]
                                for g in open_gids}
                    loadc = {}
                    for rep_name in owner_of.values():
                        if rep_name is not None:
                            loadc[rep_name] = loadc.get(rep_name, 0) + 1
                    name = (max(sorted(loadc), key=lambda n: loadc[n])
                            if loadc else sorted(router.replicas)[0])
                    victim_rep = router.replicas[name]
                    victim_rep.slow_factor = slow_factor
                    gray.update(victim=name, t_slow=clock.now())
                if slow and victim_rep is not None \
                        and gray["t_lift"] is None and i >= lift_at:
                    victim_rep.slow_factor = 1
                    gray["t_lift"] = clock.now()
                prompt, max_new = meas_reqs[i]
                try:
                    gid = router.submit([int(t) for t in prompt],
                                        max_new_tokens=max_new)
                except AdmissionFull:
                    break
                recs[gid] = {"idx": i, "toks": [], "t_first": None,
                             "state": None}
                open_gids.add(gid)
                i += 1
            stepped = False
            for rep in reps:
                if rep.alive:
                    s0 = rep.steps_done
                    try:
                        rep.pump()
                    except ReplicaError:
                        pass
                    stepped |= rep.steps_done > s0
            router.check_health()
            for gid in list(open_gids):
                try:
                    new, done, state = router.harvest(gid)
                except NoReplicaError:
                    orphaned += 1
                    new, done, state = [], True, "orphaned"
                r = recs[gid]
                if new and r["t_first"] is None:
                    r["t_first"] = clock.now()
                r["toks"].extend(new)
                if done:
                    r["state"] = state
                    open_gids.discard(gid)
            if not stepped and not open_gids and i < len(meas_reqs):
                clock.skip_to(arr[i])
            elif not stepped and open_gids:
                # nothing stepped but streams are open: only gray-
                # skipped pumps are pending. A real cluster would sit
                # in wall-clock time here — advance the virtual clock
                # by a small quantum instead of burning a real spin,
                # so the victim's crawl COSTS virtual latency (~
                # slow_factor x quantum per step when the cluster is
                # otherwise idle) without costing bench wall time
                clock.skip_to(clock.now() + 0.002)
        if slow and victim_rep is not None and gray["t_lift"] is None:
            victim_rep.slow_factor = 1        # the slowness lifts
            gray["t_lift"] = clock.now()

        def probe_round():
            # one recovery round: n_rep short probes over warmed
            # shapes, driven to completion — least_loaded spreads them
            # across the idle set, so the half-open victim gets its
            # probe placement and the breaker gets its verdict
            pr = np.concatenate([templates[0],
                                 np.arange(1, sfx_lo + 1,
                                           dtype=np.int32)])
            open_ = set()
            for _ in range(n_rep):
                try:
                    open_.add(router.submit([int(t) for t in pr],
                                            max_new_tokens=min(
                                                new_choices)))
                except AdmissionFull:
                    break
            guard = 0
            while open_ and guard < 20000:
                guard += 1
                for rep in reps:
                    if rep.alive:
                        try:
                            rep.pump()
                        except ReplicaError:
                            pass
                router.check_health()
                for g in list(open_):
                    try:
                        _, done, _ = router.harvest(g)
                    except NoReplicaError:
                        done = True
                    if done:
                        open_.discard(g)

        recovery_rounds = 0
        if defense and slow and gray["victim"] is not None:
            # the RE-CLOSE gate: tick past the cooldown and feed probe
            # traffic until the half-open probe settles the breaker
            while router.breaker_state(gray["victim"]) != "closed" \
                    and recovery_rounds < 12:
                clock.skip_to(clock.now() + cooldown + 0.01)
                probe_round()
                recovery_rounds += 1
        elapsed = clock.now() - t0
        toks = sum(len(r["toks"]) for r in recs.values())
        ttft = [r["t_first"] - arr[r["idx"]] for r in recs.values()
                if r["t_first"] is not None]
        victim = gray["victim"]
        out = {
            "slow": slow, "defense": defense,
            "tokens": toks,
            "tokens_per_sec": round(toks / max(elapsed, 1e-9), 2),
            "elapsed_s": round(elapsed, 3),
            "ttft_p50_ms": round(1e3 * float(np.percentile(ttft, 50)),
                                 1),
            "ttft_p99_ms": round(1e3 * float(np.percentile(ttft, 99)),
                                 1),
            "submitted": len(recs),
            "unfinished": sum(1 for r in recs.values()
                              if r["state"] != "finished"),
            "orphaned": orphaned,
            "failovers": router.failovers_total,
            "dead": sorted(router.dead),
            "hedges": router.hedges_total,
            "hedge_wins": router.hedge_wins_total,
            "retry_budget_exhausted":
                router.retry_budget_exhausted_total,
            "breaker_transitions": dict(router.breaker_transitions),
            "gray": dict(gray, recovery_rounds=recovery_rounds,
                         victim_breaker_final=(
                             router.breaker_state(victim)
                             if victim else None),
                         victim_alive_final=(
                             router.replicas[victim].alive
                             if victim else None)),
            "health_final": {n: h["verdict"] for n, h
                             in router.health_status().items()},
            "retraces_after_warmup": [
                r.engine.metrics()["traces"] - t
                for r, t in zip(reps, traces0)],
        }
        by_idx = {r["idx"]: r["toks"] for r in recs.values()}
        return out, by_idx

    # arrival rate anchored on a capacity probe of ONE warmed engine
    # times the replica count (same discipline as --cluster)
    probe_clock = VirtualClock()
    probe_eng = build_engine(probe_clock)
    t0 = probe_clock.now()
    for prompt, max_new in meas_reqs[: 4 * slots]:
        probe_eng.submit(prompt, max_new_tokens=max_new)
    probe_eng.run()
    cap_tps = (probe_eng.metrics()["tokens_emitted"]
               / max(probe_clock.now() - t0, 1e-9)) * n_rep
    mean_new = float(np.mean([m for _, m in meas_reqs]))
    arr_rng = np.random.RandomState(seed + 1)
    arrivals = np.cumsum(arr_rng.exponential(
        mean_new / max(load * cap_tps, 1e-9), size=len(meas_reqs)))

    healthy, healthy_toks = run_gray(slow=False, defense=True)
    defense, defense_toks = run_gray(slow=True, defense=True)
    nodef, nodef_toks = run_gray(slow=True, defense=False)

    # parity doubles as the double-billing gate: a hedge loser's
    # tokens entering the delivered stream, or a token streamed twice,
    # breaks exact equality against the undisturbed run
    parity_defense = all(defense_toks.get(i) == healthy_toks.get(i)
                         for i in range(len(meas_reqs)))
    parity_nodef = all(nodef_toks.get(i) == healthy_toks.get(i)
                       for i in range(len(meas_reqs)))
    ratio = round(defense["ttft_p99_ms"]
                  / max(nodef["ttft_p99_ms"], 1e-9), 4)

    record = {
        "metric": "gray_failure_ttft_p99_ratio",
        "value": ratio,
        "unit": "defense/no-defense TTFT p99 (lower = better defense)",
        "replicas": n_rep, "slots_per_replica": slots,
        "requests": n_meas, "offered_load": load, "seed": seed,
        "slow_factor": slow_factor, "slow_at": slow_at,
        "lift_at": lift_at,
        "healthy": healthy,
        "defense": defense,
        "no_defense": nodef,
        "token_parity_defense_vs_healthy": parity_defense,
        "token_parity_no_defense_vs_healthy": parity_nodef,
        "layers": L, "hidden": E, "vocab": V,
        "device": _device_record(dev),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "gray_failure", record)
    print(json.dumps(record))

    rc = 0
    if not parity_defense:
        print("bench_serving: GRAY-DRILL TOKEN PARITY BROKE (defense "
              "run) — hedged dispatch is not greedy-identical, or a "
              "loser leg's tokens were double-billed", file=sys.stderr)
        rc = 1
    if not parity_nodef:
        print("bench_serving: GRAY-DRILL TOKEN PARITY BROKE "
              "(no-defense run) — slowness alone must never change "
              "delivered tokens", file=sys.stderr)
        rc = 1
    for run in (healthy, defense, nodef):
        tag = (f"slow={run['slow']} defense={run['defense']}")
        if run["orphaned"] or run["unfinished"] \
                or run["submitted"] != n_meas:
            print(f"bench_serving: GRAY DRILL DROPPED STREAMS ({tag}) "
                  f"— submitted={run['submitted']}/{n_meas}, "
                  f"unfinished={run['unfinished']}, "
                  f"orphaned={run['orphaned']}", file=sys.stderr)
            rc = 1
        if run["failovers"] or run["dead"]:
            print(f"bench_serving: GRAY DRILL DECLARED DEATH ({tag}) "
                  f"— failovers={run['failovers']}, "
                  f"dead={run['dead']}; a slow-but-alive replica must "
                  "be shed by the breaker, never killed",
                  file=sys.stderr)
            rc = 1
        if any(run["retraces_after_warmup"]):
            print(f"bench_serving: RETRACES AFTER WARMUP ({tag}): "
                  f"{run['retraces_after_warmup']} — the defense "
                  "stack must be pure host code", file=sys.stderr)
            rc = 1
    if defense["breaker_transitions"]["open"] < 1 \
            or defense["gray"]["victim"] is None:
        print("bench_serving: the gray drill never OPENED the breaker "
              f"({defense['breaker_transitions']}) — the injected "
              "slowness went undetected", file=sys.stderr)
        rc = 1
    if defense["hedge_wins"] < 1:
        print("bench_serving: no hedge WON during the defense run "
              f"(hedges={defense['hedges']}, "
              f"wins={defense['hedge_wins']}) — the drill must "
              "exercise the promotion path", file=sys.stderr)
        rc = 1
    if defense["gray"]["victim_breaker_final"] != "closed":
        print("bench_serving: the victim's breaker never RE-CLOSED "
              "after the slowness lifted (final="
              f"{defense['gray']['victim_breaker_final']}, "
              f"recovery_rounds={defense['gray']['recovery_rounds']})",
              file=sys.stderr)
        rc = 1
    if defense["ttft_p99_ms"] > 0.5 * nodef["ttft_p99_ms"]:
        print("bench_serving: GRAY TAIL NOT BOUNDED — defense TTFT "
              f"p99 {defense['ttft_p99_ms']}ms vs no-defense "
              f"{nodef['ttft_p99_ms']}ms (ratio {ratio}, gate 0.5)",
              file=sys.stderr)
        rc = 1
    if nodef["ttft_p99_ms"] < 1.5 * healthy["ttft_p99_ms"]:
        print("bench_serving: the slow injection had NO EFFECT — "
              f"no-defense TTFT p99 {nodef['ttft_p99_ms']}ms vs "
              f"healthy {healthy['ttft_p99_ms']}ms; nothing was "
              "defended against (tune BENCH_GRAY_SLOW_FACTOR?)",
              file=sys.stderr)
        rc = 1
    return rc


def main_qos():
    """The OVERLOAD QoS chaos drill: one paged engine, mixed-class
    (high/normal/low) fixed-seed Poisson traffic at BENCH_QOS_LOAD
    (default 2x) the engine's measured capacity. Graceful degradation
    is the product under test: strictly better arrivals preempt
    running low-class slots into the host parking lot, parked sessions
    resume when pressure clears, the weighted-fair packer splits
    prefill budget by class share — and NONE of it may cost a token,
    a request, or a retrace. Gates (exit 1): exact greedy parity for
    every request vs an unloaded oracle run, >= 1 preemption fired
    with zero aborted/expired requests and every admitted request
    finished, the high class p99 TTFT within BENCH_QOS_SLO_X
    (default 4x) of the unloaded p99 while the low class degraded
    past that line, zero retraces after warmup. Lands under "qos" in
    BENCH_serving.json (other modes' records preserved)."""
    jax, dev = _require_tpu()
    import numpy as np

    from paddle_tpu.inference.serving import AdmissionFull, ServingEngine

    slots = int(os.environ.get("BENCH_SLOTS", "8"))
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "4"))
    cap_ = int(os.environ.get("BENCH_PAGED_CAP", "16"))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS", str(6 * slots)))
    load = float(os.environ.get("BENCH_QOS_LOAD", "2.0"))
    slo_x = float(os.environ.get("BENCH_QOS_SLO_X", "4.0"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))

    fmt, embed, head, (E, H, FF, L, V) = _build_model()

    rng = np.random.RandomState(seed)
    classes = ("high", "normal", "low")

    def make(n):
        reqs = []
        for _ in range(n):
            plen = int(rng.randint(6, 25))
            max_new = int(rng.choice([16, 24, 32]))
            prio = str(rng.choice(classes, p=[.25, .45, .30]))
            reqs.append((rng.randint(1, V, (plen,)).astype("int32"),
                         max_new, prio))
        return reqs

    bucket_reqs = [(rng.randint(1, V, (p,)).astype("int32"), 4)
                   for p in (8, 16, 24)]
    warm_reqs = make(2 * slots)
    meas_reqs = make(n_meas)

    def new_engine(clock):
        return ServingEngine(fmt, embed, head, num_slots=slots,
                             max_seq_len=smax, decode_chunk=chunk,
                             prefill_cap=cap_, paged=True,
                             clock=clock.now)

    # ---- unloaded oracle: every request SOLO on a fresh engine — the
    # greedy want-tokens for the parity gate and the unloaded TTFT
    # distribution the SLO line is drawn from
    oclock = VirtualClock()
    oracle = new_engine(oclock)
    for prompt, max_new in bucket_reqs:
        oracle.submit(prompt, max_new_tokens=max_new)
        oracle.run()
    want, ttft_unloaded = [], []
    for prompt, max_new, prio in meas_reqs:
        rid = oracle.submit(prompt, max_new_tokens=max_new,
                            priority=prio)
        oracle.run()
        want.append(oracle.results[rid]["tokens"].tolist())
        ttft_unloaded.append(oracle.results[rid]["ttft_s"])
    ttft_un_p99 = float(np.percentile(ttft_unloaded, 99))
    slo_s = slo_x * ttft_un_p99

    # ---- measured engine: compile warmup (buckets solo), capacity
    # estimate, then ONE forced preempt/resume cycle so the KV
    # export/import helpers are warm before the retrace gate arms
    clock = VirtualClock()
    eng = new_engine(clock)
    for prompt, max_new in bucket_reqs:
        eng.submit(prompt, max_new_tokens=max_new)
        eng.run()
    for prompt, max_new, _prio in warm_reqs:
        try:
            eng.submit(prompt, max_new_tokens=max_new)
        except AdmissionFull:
            eng.run()
            eng.submit(prompt, max_new_tokens=max_new)
    eng.run()
    eng.reset_metrics(keep_results=False)
    t0 = clock.now()
    for prompt, max_new, _prio in warm_reqs[:slots]:
        eng.submit(prompt, max_new_tokens=max_new)
    eng.run()
    cap_tps = eng.metrics()["tokens_emitted"] / max(clock.now() - t0,
                                                    1e-9)
    lows = [eng.submit(rng.randint(1, V, (12,)).astype("int32"),
                       max_new_tokens=24, priority="low")
            for _ in range(slots)]
    while not all(eng._req_index[r].tokens for r in lows):
        eng.step()
    eng.submit(rng.randint(1, V, (12,)).astype("int32"),
               max_new_tokens=8, priority="high")
    eng.run()
    if not eng.metrics()["requests_preempted"]:
        print("bench_serving: qos warmup never preempted — the "
              "park/resume path is cold", file=sys.stderr)
    traces_warm = eng.metrics()["traces"]
    eng.reset_metrics(keep_results=False)

    # ---- measured phase: mixed-class Poisson at `load` x capacity
    mean_new = float(np.mean([m for _, m, _ in meas_reqs]))
    rate = load * cap_tps / mean_new
    arr_rng = np.random.RandomState(seed + 1)
    arrivals = np.cumsum(
        arr_rng.exponential(1.0 / rate, size=n_meas)) + clock.now()

    sub = {}
    i = 0
    t_start = clock.now()
    while i < n_meas or eng.has_work:
        now = clock.now()
        while i < n_meas and arrivals[i] <= now:
            prompt, max_new, prio = meas_reqs[i]
            try:
                rid = eng.submit(prompt, max_new_tokens=max_new,
                                 priority=prio)
            except AdmissionFull:
                break                    # honest backpressure: retry
            sub[rid] = (i, clock.now())
            i += 1
        if not eng.has_work:
            clock.skip_to(arrivals[i])
            continue
        eng.step()
    elapsed = clock.now() - t_start
    m = eng.metrics()

    # per-class TTFT from ARRIVAL (queueing + park time included) and
    # the parity sweep against the unloaded oracle
    ttft_by = {c: [] for c in classes}
    parity_bad = drops = 0
    for rid, (j, t_sub) in sub.items():
        r = eng.results.get(rid)
        if r is None or r["expired"]:
            drops += 1
            continue
        if r["tokens"].tolist() != want[j]:
            parity_bad += 1
        wait = t_sub - arrivals[j]
        ttft_by[meas_reqs[j][2]].append(wait + r["ttft_s"])
    p99 = {c: (round(1e3 * float(np.percentile(v, 99)), 1) if v
               else None) for c, v in ttft_by.items()}
    high_p99_s = (p99["high"] or 0.0) / 1e3
    low_p99_s = (p99["low"] or 0.0) / 1e3

    record = {
        "metric": "serving_qos_high_ttft_p99_over_unloaded_x",
        "value": round(high_p99_s / max(ttft_un_p99, 1e-9), 2),
        "unit": "x unloaded p99 TTFT (gate: <= slo_x under overload)",
        "offered_load": load, "slo_x": slo_x,
        "slo_ms": round(1e3 * slo_s, 1),
        "ttft_unloaded_p99_ms": round(1e3 * ttft_un_p99, 1),
        "ttft_p99_ms_by_class": p99,
        "requests": n_meas,
        "requests_by_class": {c: sum(1 for _, _m, p in meas_reqs
                                     if p == c) for c in classes},
        "tokens_by_class": {c: m[f"tokens_emitted_{c}"]
                            for c in classes},
        "preemptions": m["requests_preempted"],
        "resumes": m["requests_resumed"],
        "expired": m["requests_expired"],
        "dropped_admitted": drops,
        "parity_bad": parity_bad,
        "retraces_after_warmup": m["traces"] - traces_warm,
        "capacity_tokens_per_sec": round(cap_tps, 2),
        "elapsed_s": round(elapsed, 3),
        "num_slots": slots, "max_seq": smax, "block_tokens": cap_,
        "layers": L, "hidden": E, "vocab": V, "seed": seed,
        "device": _device_record(dev),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "qos", record)
    print(json.dumps(record))
    rc = 0
    if parity_bad:
        print(f"bench_serving: QOS PARITY BROKE — {parity_bad} "
              "request(s) diverged from the unloaded oracle (a "
              "preempt/resume or packing decision corrupted a stream)",
              file=sys.stderr)
        rc = 1
    if drops or m["requests_expired"] or len(sub) != n_meas \
            or m["requests_finished"] != n_meas:
        print(f"bench_serving: ADMITTED WORK WAS DROPPED — "
              f"submitted {len(sub)}/{n_meas}, finished "
              f"{m['requests_finished']}, expired "
              f"{m['requests_expired']}, lost {drops}; overload must "
              "delay the low class, never abort it", file=sys.stderr)
        rc = 1
    if not m["requests_preempted"] or \
            m["requests_resumed"] != m["requests_preempted"]:
        print(f"bench_serving: preemption never exercised or never "
              f"recovered (preempted={m['requests_preempted']} "
              f"resumed={m['requests_resumed']}) — the drill needs "
              "real slot pressure", file=sys.stderr)
        rc = 1
    if high_p99_s > slo_s:
        print(f"bench_serving: HIGH-CLASS SLO RED under overload — "
              f"p99 TTFT {p99['high']}ms > {round(1e3 * slo_s, 1)}ms "
              f"({slo_x}x unloaded p99)", file=sys.stderr)
        rc = 1
    if low_p99_s <= slo_s:
        print(f"bench_serving: the low class did NOT degrade "
              f"(p99 {p99['low']}ms <= the {round(1e3 * slo_s, 1)}ms "
              "SLO line) — the drill is not actually overloaded; "
              "raise BENCH_QOS_LOAD", file=sys.stderr)
        rc = 1
    if record["retraces_after_warmup"]:
        print("bench_serving: RETRACES AFTER WARMUP during the QoS "
              "drill — class churn and park/resume must be pure host "
              "data", file=sys.stderr)
        rc = 1
    return rc


def main_disagg():
    """Disaggregated prefill/decode A/B (see the module docstring):
    the SAME fixed-seed long/short Poisson arrivals on one MIXED
    engine vs a PREFILL+DECODE role-split cluster at equal total
    slots, streamed KV handoff on. Both sides run router-driven on
    their own virtual clock so TTFT/ITL are arrival-anchored and the
    handoff machinery itself (export, staged stream, import, adopt)
    is inside the measured window. Gates (exit 1): exact greedy
    parity per request, zero drops/orphans/failovers, every session
    handed off exactly once, zero prompt recompute, zero retraces
    after warmup on every engine, decode ITL p99 within
    BENCH_DISAGG_ITL_X of mixed."""
    jax, dev = _require_tpu()
    import numpy as np

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.serving_cluster import LocalReplica, Router

    slots = int(os.environ.get("BENCH_SLOTS", "4"))
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "4"))
    cap_ = int(os.environ.get("BENCH_PAGED_CAP", "16"))
    n_meas = int(os.environ.get("BENCH_SERVE_REQUESTS", str(12 * slots)))
    load = float(os.environ.get("BENCH_SERVE_LOAD", "1.0"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "0"))
    long_frac = float(os.environ.get("BENCH_CHUNKED_LONG", "0.4"))
    # decode ITL p99 tolerance vs mixed. NOT 1.0: this harness pumps
    # both engines from ONE thread, so a decode token gap can carry a
    # whole prefill-chunk pump from the other engine — time real
    # role-split hardware overlaps. Measured here: p50 at parity,
    # p99 ~2.8x from exactly those serialization points. The gate is
    # a gross-regression tripwire (a handoff stalling decode shows up
    # as 10x+); the recorded ratio is what to trend
    itl_x = float(os.environ.get("BENCH_DISAGG_ITL_X", "4.0"))
    hb = int(os.environ.get("BENCH_DISAGG_HANDOFF_BLOCKS", "1"))
    # prefix pool sized to hold every live session's blocks twice
    # over: the pool is what makes prefill_tokens_computed/saved
    # accounting (the zero-recompute gate) and streamed staging real
    pool_blocks = 4 * slots * max(smax // cap_, 1)

    fmt, embed, head, (E, H, FF, L, V) = _build_model()
    rng = np.random.RandomState(seed)

    # warmup covers the prefill buckets both the short and long arms
    # of the workload hit, plus workload-shaped waves that exercise
    # hold/export on the prefill engine and import/decode on the
    # decode engine (driven THROUGH the router below, so the handoff
    # executables compile before the retrace gate arms)
    bucket_reqs = [(rng.randint(1, V, (p,)).astype("int32"), 4)
                   for p in (8, 16, 32, 64, 128, 160)]
    warm_reqs = _make_longprompt_workload(rng, 4 * slots, V, smax,
                                          long_frac)
    meas_reqs = _make_longprompt_workload(rng, n_meas, V, smax,
                                          long_frac)
    total_prompt = sum(int(p.size) for p, _ in meas_reqs)

    def _env_f(name):
        v = os.environ.get(name)
        return float(v) if v not in (None, "") else None
    slo_ttft = _env_f("BENCH_SLO_TTFT_S")
    slo_itl = _env_f("BENCH_SLO_ITL_S")
    slo_e2e = _env_f("BENCH_SLO_E2E_S")

    def mk_engine(clock, role, ns):
        return ServingEngine(fmt, embed, head, num_slots=ns,
                             max_seq_len=smax, decode_chunk=chunk,
                             prefill_cap=cap_, paged=True,
                             prefix_cache_blocks=pool_blocks,
                             role=role, clock=clock.now)

    def run_side(disagg, arrivals=None):
        clock = VirtualClock()
        if disagg:
            engs = [mk_engine(clock, "prefill", slots),
                    mk_engine(clock, "decode", slots)]
            names = ("prefill0", "decode0")
        else:
            engs = [mk_engine(clock, "mixed", 2 * slots)]
            names = ("mixed0",)
        reps = [LocalReplica(n, e, threaded=False, clock=clock.now)
                for n, e in zip(names, engs)]
        router = Router(reps, snap_max_age_s=0.0, clock=clock.now,
                        handoff_blocks=(hb if disagg else None))
        warm = bucket_reqs + warm_reqs
        _drive_cluster(router, reps, clock, warm,
                       np.zeros(len(warm)) + clock.now())
        for e in engs:
            e.reset_metrics(keep_results=False)
        # capacity probe on the warm wave (the mixed side's estimate
        # sets the shared arrival process)
        t0 = clock.now()
        _drive_cluster(router, reps, clock, warm_reqs,
                       np.zeros(len(warm_reqs)) + clock.now())
        cap = sum(e.metrics()["tokens_emitted"] for e in engs) \
            / max(clock.now() - t0, 1e-9)
        traces0 = [e.metrics()["traces"] for e in engs]
        handoffs0 = router.handoffs_total
        for e in engs:
            e.reset_metrics(keep_results=False)

        if arrivals is None:
            mean_new = float(np.mean([m for _, m in meas_reqs]))
            rate = load * cap / mean_new
            arr_rng = np.random.RandomState(seed + 1)
            arrivals = np.cumsum(
                arr_rng.exponential(1.0 / rate, size=n_meas))
        arr = arrivals + clock.now()
        t0 = clock.now()
        recs, _ = _drive_cluster(router, reps, clock, meas_reqs, arr)
        elapsed = clock.now() - t0

        toks = sum(len(r["toks"]) for r in recs.values())
        got, ttft, itl, slo_ok = {}, [], [], 0
        unfinished = 0
        for r in recs.values():
            got[r["idx"]] = r["toks"]
            if r["t_first"] is None or r["t_done"] is None:
                unfinished += 1
                continue
            t_arr = arr[r["idx"]]
            tf = r["t_first"] - t_arr
            e2e = r["t_done"] - t_arr
            ttft.append(tf)
            gap = ((r["t_done"] - r["t_first"])
                   / max(len(r["toks"]) - 1, 1))
            if len(r["toks"]) > 1:
                itl.append(gap)
            ok = ((slo_ttft is None or tf <= slo_ttft)
                  and (slo_itl is None or gap <= slo_itl)
                  and (slo_e2e is None or e2e <= slo_e2e))
            slo_ok += int(ok)

        def pctl(v, q):
            return round(1e3 * float(np.percentile(v, q)), 2) \
                if v else None
        side = {
            "roles": {n: e.role for n, e in zip(names, engs)},
            "tokens": toks,
            "tokens_per_sec": round(toks / max(elapsed, 1e-9), 2),
            "capacity_tokens_per_sec": round(cap, 2),
            "ttft_p50_ms": pctl(ttft, 50), "ttft_p99_ms": pctl(ttft, 99),
            "itl_p50_ms": pctl(itl, 50), "itl_p99_ms": pctl(itl, 99),
            "slo": {"ok": slo_ok, "violated": len(recs) - slo_ok},
            "retraces_after_warmup": sum(
                e.metrics()["traces"] - t for e, t in zip(engs, traces0)),
            "elapsed_s": round(elapsed, 3),
        }
        info = {"engs": engs, "router": router, "recs": recs,
                "got": got, "unfinished": unfinished,
                "handoffs": router.handoffs_total - handoffs0,
                "itl": itl}
        return side, info, arrivals

    side_m, info_m, arrivals = run_side(False)
    side_d, info_d, _ = run_side(True, arrivals)

    eng_p, eng_d = info_d["engs"]
    mp, md = eng_p.metrics(), eng_d.metrics()
    mm = info_m["engs"][0].metrics()
    itl_ratio = ((side_d["itl_p99_ms"] or 0.0)
                 / max(side_m["itl_p99_ms"] or 0.0, 1e-9))

    record = {
        "metric": "serving_disagg_decode_itl_p99_over_mixed_x",
        "value": round(itl_ratio, 3),
        "unit": "x mixed decode ITL p99 (gate: <= itl_x)",
        "itl_x": itl_x, "offered_load": load,
        "handoff_blocks": hb, "long_frac": long_frac,
        "requests": n_meas,
        "slots": {"mixed": 2 * slots, "prefill": slots,
                  "decode": slots},
        "mixed": side_m, "disagg": side_d,
        "handoffs": info_d["handoffs"],
        "failovers": info_d["router"].failovers_total,
        "migration_aborts": info_d["router"].migration_aborts_total,
        "kv_blocks_shipped": mp["kv_blocks_shipped"],
        "kv_blocks_adopted": md["kv_blocks_adopted"],
        "prefill_tokens": {
            "submitted": total_prompt,
            "computed_prefill": mp["prefill_tokens_computed"],
            "saved_prefill": mp["prefill_tokens_saved"],
            "computed_decode": md["prefill_tokens_computed"],
            "computed_mixed": mm["prefill_tokens_computed"],
            "saved_mixed": mm["prefill_tokens_saved"],
        },
        "num_slots": 2 * slots, "max_seq": smax, "block_tokens": cap_,
        "layers": L, "hidden": E, "vocab": V, "seed": seed,
        "device": _device_record(dev),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    _write_merged(path, None, "disagg", record)
    print(json.dumps(record))

    rc = 0
    parity_bad = sum(1 for i in range(n_meas)
                     if info_d["got"].get(i) != info_m["got"].get(i))
    if parity_bad or len(info_d["got"]) != n_meas \
            or len(info_m["got"]) != n_meas:
        print(f"bench_serving: DISAGG PARITY BROKE — {parity_bad} "
              f"request(s) diverged from the mixed run "
              f"(disagg={len(info_d['got'])}/{n_meas} mixed="
              f"{len(info_m['got'])}/{n_meas} finished); a KV handoff "
              "corrupted or dropped a stream", file=sys.stderr)
        rc = 1
    if info_d["unfinished"] or info_m["unfinished"]:
        print(f"bench_serving: ADMITTED WORK WAS DROPPED — "
              f"{info_d['unfinished']} disagg / "
              f"{info_m['unfinished']} mixed session(s) never "
              "finished", file=sys.stderr)
        rc = 1
    if info_d["handoffs"] != n_meas \
            or record["failovers"] or record["migration_aborts"]:
        print(f"bench_serving: HANDOFF ACCOUNTING OFF — "
              f"{info_d['handoffs']}/{n_meas} handoffs, "
              f"{record['failovers']} failover(s), "
              f"{record['migration_aborts']} abort(s); every session "
              "must ship prefill->decode exactly once, no replays",
              file=sys.stderr)
        rc = 1
    pt = record["prefill_tokens"]
    if md["prefill_tokens_computed"] != 0 \
            or pt["computed_prefill"] + pt["saved_prefill"] \
            != total_prompt \
            or mp["kv_blocks_shipped"] != md["kv_blocks_adopted"] \
            or not mp["kv_blocks_shipped"]:
        print(f"bench_serving: PROMPT RECOMPUTE ON THE DECODE TIER — "
              f"decode computed {pt['computed_decode']} prefill "
              f"tokens, prefill computed+saved "
              f"{pt['computed_prefill']}+{pt['saved_prefill']} of "
              f"{total_prompt} submitted, shipped/adopted "
              f"{mp['kv_blocks_shipped']}/{md['kv_blocks_adopted']}; "
              "the KV wire must carry every prompt block exactly once",
              file=sys.stderr)
        rc = 1
    if side_d["retraces_after_warmup"] or side_m["retraces_after_warmup"]:
        print(f"bench_serving: RETRACES AFTER WARMUP — "
              f"disagg {side_d['retraces_after_warmup']}, mixed "
              f"{side_m['retraces_after_warmup']}; role split and "
              "streamed handoff must be pure host-side data movement",
              file=sys.stderr)
        rc = 1
    if side_d["itl_p99_ms"] is not None and side_m["itl_p99_ms"] \
            and itl_ratio > itl_x:
        print(f"bench_serving: DECODE ITL REGRESSED — disagg p99 "
              f"{side_d['itl_p99_ms']}ms > {itl_x}x mixed p99 "
              f"{side_m['itl_p99_ms']}ms; isolating decode from "
              "prefill interference is the point of the split",
              file=sys.stderr)
        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
