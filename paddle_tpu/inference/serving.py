"""Continuous-batching serving engine over the stacked KV ring cache.

Capability parity: the serving loop the reference's AnalysisPredictor +
fused_multi_transformer stack is deployed behind (and the Orca/vLLM-style
slot scheduling production LLM serving converged on), realized TPU-style
on top of FusedDecoder's machinery:

  * ONE decode step is compiled for a fixed shape — B cache slots over
    the stacked ring buffer [L, 2, B, H, Smax, D] — and stays hot while
    requests churn through the slots. Admission, completion, and slot
    reuse are pure DATA (per-slot `cache_lens`, active masks, per-slot
    sampling params all ride in as arrays), so request churn causes ZERO
    retraces and zero recompiles after warmup.
  * Each slot decodes at its OWN depth: the per-row position path in
    generation.py (vector `t`) drives the same Pallas flash-decode
    kernels, which always took per-row `cache_lens`.
  * In-slot prefill: a freed slot is overwritten by the next queued
    request via the chunked prefill scan with a per-row WRITE MASK —
    non-admitted rows' live cache rows are untouchable by construction
    (masked rows scatter out of bounds and are dropped).
  * Slot eviction = resetting `cache_lens[b]` host-side; nothing is
    zeroed. The decode_attention write kernels' `cache_lens < Smax`
    invariant (enforced at submit: prompt + max_new_tokens <= Smax)
    guarantees a dead slot can never write out of its row.

Host control happens only at chunk boundaries: every `decode_chunk`
tokens the engine harvests per-slot streams, completes finished
requests, admits from the queue, and emits a metrics record (tokens/s,
TTFT, queue depth, slot occupancy, step latency, trace count).

Automatic prefix caching (`prefix_cache_blocks=` / a shared
`PrefixCache`): admission first splats the longest PUBLISHED prefix of
the prompt into the slot's cache row — one compiled block gather-copy
over a pow-2 chain-length ladder, write-masked like in-slot prefill —
and only the uncached suffix runs through the chunked prefill scan; as
prefill lands, the prompt's full `prefill_cap`-sized blocks are
committed back to the pool (copy-out, dedup'd) so later shared-prompt
requests hit. See prefix_cache.py for the radix store / COW invariants.

Paged KV cache (default; `PADDLE_SERVING_PAGED=0` keeps the dense
per-slot ring for parity testing): ONE BlockPool
`[L, 2, NBtotal, H, Bt, D]` holds every KV block — slots, prefix-cache
entries, and spec-verify writes — and each slot's sequence is a block
TABLE `[Smax/Bt]` of pool indices living here as pure data
(paged_kv.py). Decode/verify attention gathers through the table
(paged Pallas kernels / gather-dense fallback), K/V writes scatter
through it under the same `cache_lens < Smax` clamp discipline, prefix
hits become index writes (zero-copy adopt, zero-copy publish), blocks
map lazily as `lens` grows and free on eviction, and copy-on-write
makes `fork_slot` (parallel sampling) nearly free. Slot capacity is
bounded by the POOL, not `B x Smax`: `kv_pool_blocks=` /
`PADDLE_SERVING_KV_BLOCKS` states a memory budget (explicitly sized
pools shed honestly with `AdmissionFull` when commitments exceed it);
the default sizing `B x Smax/Bt` equals the dense HBM footprint and
never sheds. `metrics()` exposes `kv_blocks_used/free/total`.

Token-budget scheduling (default; `token_budget=` /
`PADDLE_SERVING_TOKEN_BUDGET`, 0 restores the legacy phase-prefill
scheduler): every compiled step spends a fixed token budget mixing
decode rows (one input token + any draft claim each) with prefill
chunks from admitted-but-unprefilled slots — Sarathi-style chunked
prefill. Admission is pure bookkeeping (slots enter a `prefilling`
state; the budget packer advances them through spare step capacity),
so one long prompt can no longer hold the whole decode gang hostage
and TTFT p99 stays flat under load. The ONE [B, C]-column budget core
(generation._build_budget_core) generalizes the spec-verify block to
per-row segment lengths: segments, drafts, prefill cursors are all
data, so every packing the scheduler can emit reuses one executable.
Sampled mode draws each token from fold_in(request_seed, position)
(generation._sample_rows), making sampled outputs EXACTLY invariant
to the scheduler — the chunked-vs-phase parity tests pin token
equality in both greedy and sampled mode.

Token-FLATTENED budget layout (`PADDLE_SERVING_FLAT_BUDGET=1` /
`flat_budget=True`; row-aligned stays the default): the [B, C] block
computes every masked column — a lone long prefill wastes (B-1) x C
positions per dispatch. Flat mode packs the SAME work as ONE ragged
[T] token stream (a B-wide decode region plus back-to-back segments
with eighth-octave ladder width) with per-token (slot, pos) indices,
so T real tokens cost ~T computed positions (`budget_padding_tokens`
~ 0) and one prefill segment can span the whole spare budget, not C
columns; prefill chunks attend via a block-flash Pallas kernel
(decode_attention_paged_flat) with the gather-dense fallback as the
parity path. Token outputs are EXACTLY the row layout's, greedy and
sampled (tests/test_flat_budget.py).

Telemetry (telemetry.py; `telemetry_ring=` / `PADDLE_TELEMETRY_RING`,
0 disables collection): per-request lifecycle spans and a per-dispatch
step timeline in bounded rings, TTFT/latency/tokens-per-step as
fixed-size log-bucketed histograms (the `metrics()` percentile source —
no unbounded scans), `metrics_prometheus()` text exposition with
counters monotonic across `reset_metrics`, `telemetry_snapshot()` as
the cluster-router payload, and
`telemetry.export_chrome_tracing(engine, path)` for Perfetto. All of
it is host bookkeeping: telemetry on adds ZERO device dispatches and
leaves the zero-retrace contract untouched.

Speculative decoding (`spec_k=` / `PADDLE_SERVING_SPEC_K`): a per-slot
model-free n-gram drafter (spec_decode.py) proposes up to K tokens per
step from the request's own context; ONE compiled K+1-position verify
step (generation._build_verify_core) scores them all, and
acceptance/rollback runs here as pure data over the returned logits —
greedy outputs stay token-identical to spec off, sampled outputs keep
the exact target distribution via rejection sampling. Slots with no
usable draft ride along all-masked (the step degrades to a normal
decode step for them), and a thin-draft scheduler heuristic falls back
to the plain decode chunk — both executables are warm, so churn stays
zero-retrace either way.
"""
from __future__ import annotations

import itertools
import os
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ..core.rng import next_key
from ..tensor.tensor import Tensor, no_grad
from .generation import (FusedDecoder, _absmax_int8, _host_seed,
                         _sample_rows, dispatch_kind)
from .telemetry import (COUNTER_FOLD_KEYS, DEFAULT_QOS_SHARES,
                        DEFAULT_RING, QOS_CLASSES, QOS_DEFAULT, QOS_RANK,
                        SloPolicy, Telemetry)

__all__ = ["ServingEngine", "ServedRequest", "AdmissionFull",
           "QOS_CLASSES"]


class AdmissionFull(RuntimeError):
    """submit() rejected: the pending queue is at max_pending (overload
    shedding — the caller backs off or routes elsewhere; the engine never
    buffers unboundedly)."""


class ServedRequest:
    """One request's lifecycle record. States: queued -> running ->
    finished | expired. Times come from the engine clock (injectable for
    virtual-time benchmarking); `ttft_s`/`latency_s` are measured from
    submit."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_token_id",
                 "min_length", "repetition_penalty", "state", "slot",
                 "tokens", "t_submit", "t_admit", "t_first", "t_done",
                 "deadline_s", "seed", "trace_id", "attempt", "priority")

    def __init__(self, rid, prompt, max_new_tokens, eos_token_id,
                 min_length, repetition_penalty, t_submit,
                 deadline_s=None, seed=0, trace_id=None, attempt=1,
                 priority=QOS_DEFAULT):
        self.rid = rid
        self.prompt = prompt                      # np.int32 [S]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.min_length = int(min_length)
        self.repetition_penalty = float(repetition_penalty)
        self.state = "queued"
        self.slot = None
        self.tokens = []                          # generated token ids
        self.t_submit = t_submit
        self.t_admit = None                       # slot entry time
        self.t_first = None                       # first token time
        self.t_done = None
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        # per-request sampling seed: the engine's sampled mode draws
        # each generated token from fold_in(PRNGKey(seed), position),
        # so outputs are invariant to scheduling (see _sample_rows)
        self.seed = int(seed)
        # cluster trace context: the gateway/router thread one trace id
        # through every placement of one client request; attempt
        # increments across failover re-submits (telemetry.RequestTrace
        # carries both, so cross-replica spans join on the trace id)
        self.trace_id = None if trace_id is None else str(trace_id)
        self.attempt = int(attempt)
        # QoS class (telemetry.QOS_CLASSES, best first): drives the
        # admission order, the weighted-fair budget shares, and
        # preemption-victim selection — all pure host data
        self.priority = priority

    @property
    def ttft_s(self):
        return (None if self.t_first is None
                else self.t_first - self.t_submit)

    @property
    def latency_s(self):
        return (None if self.t_done is None
                else self.t_done - self.t_submit)

    def result(self):
        return {"rid": self.rid, "tokens": np.asarray(self.tokens,
                                                      np.int32),
                "ttft_s": self.ttft_s, "latency_s": self.latency_s,
                "expired": self.state == "expired"}


class ServingEngine:
    """Slot-based continuous batching over FusedDecoder's compiled step.

    API sketch::

        eng = ServingEngine(fmt, embed, head, num_slots=8,
                            max_seq_len=1024)
        rid = eng.submit(prompt_ids, max_new_tokens=64, eos_token_id=2)
        eng.run()                       # drive until queue + slots drain
        out = eng.results[rid]["tokens"]
        eng.metrics()                   # aggregate engine counters

    `results` retains the most recent `telemetry_ring` finished
    requests (default 2048, `PADDLE_TELEMETRY_RING`) — a long-lived
    service must harvest each result promptly rather than index
    arbitrarily old rids; aggregate totals survive in `metrics()` and
    the Prometheus lifetime counters.

    Streaming readers (the cluster gateway's SSE path) must NOT race
    that cap: `track(rid)` registers an incremental cursor, and a
    tracked request's record is RETAINED past the results cap until
    `harvest_new_tokens(rid)` has returned `done=True` (or
    `release(rid)` drops the cursor). Call `track` before the request
    can finish — registering only after finish falls back to the
    bounded `results` dict, which may already have evicted the entry
    (KeyError, the documented race). `poll(rid)` is the non-destructive
    status read; neither API moves any counter.

    Sampling mode (greedy / top-k / top-p / temperature) is ENGINE
    config — it is baked into the one compiled step. Per-REQUEST knobs
    (eos_token_id, max_new_tokens, min_length, repetition_penalty) are
    data: [B] arrays the compiled step reads, so they never retrace.
    repetition_penalty needs the [B, V] presence-mask carry; enable it
    at construction (`enable_repetition_penalty=True`) — the flag is
    static trace structure. `spec_k=K` turns on speculative decoding
    (see the module docstring): K, like the sampling mode, is baked
    into the ONE compiled verify step; drafts and acceptance are data.
    """

    def __init__(self, fmt, embed, head, num_slots, max_seq_len,
                 do_sample=False, top_k=0, top_p=1.0, temperature=1.0,
                 decode_chunk=None, use_rotary=False,
                 enable_repetition_penalty=False, clock=None,
                 max_pending=None, prefill_cap=None,
                 prefix_cache_blocks=0, prefix_cache=None, spec_k=None,
                 paged=None, kv_pool=None, kv_pool_blocks=None,
                 token_budget=None, flat_budget=None,
                 telemetry_ring=None, slo=None, role=None,
                 weight_quant=None, kv_quant=None):
        # first-class quant config rides the decoder ctor: explicit
        # args win, None defers to the PADDLE_TPU_DECODE_* env knobs;
        # FusedDecoder fail-fasts unknown modes and int4-unpackable
        # model axes (see its ctor / _validate_int4_dims)
        self.dec = FusedDecoder(fmt, embed, head, max_seq_len,
                                use_rotary=use_rotary,
                                weight_quant=weight_quant,
                                kv_quant=kv_quant)
        self.num_slots = int(num_slots)
        # disaggregated serving role (PADDLE_ROLE): "mixed" (default)
        # is today's behavior — prefill and decode share this engine.
        # "prefill" runs prompt processing only: a slot whose prompt
        # completes (first token sampled) is HELD as state "prefilled"
        # (active=False, KV + slot resident) until the cluster router
        # ships it to a decode replica via export_slot/import_slot —
        # the DistServe/Splitwise split that keeps long prompts from
        # stalling decode inter-token latency. "decode" engines run
        # normally (role enforcement is placement-side: the router
        # never routes fresh prompts at them); their import path is
        # the handoff landing zone.
        role = (role if role is not None
                else os.environ.get("PADDLE_ROLE", "mixed"))
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"role must be one of ('prefill', 'decode', 'mixed'), "
                f"got {role!r}")
        self.role = role
        self.smax = self.dec.smax
        self.do_sample = bool(do_sample)
        self.top_k, self.top_p = top_k, top_p
        self.temperature = temperature
        self.decode_chunk = int(decode_chunk or
                                os.environ.get("PADDLE_TPU_SERVE_CHUNK",
                                               "4"))
        # pow-2 prefill ladder cap — ONE knob tunes both the prefill
        # chunk ladder and the prefix-cache block size (blocks are
        # prefill-chunk-aligned by construction)
        cap = int(prefill_cap if prefill_cap is not None
                  else os.environ.get("PADDLE_SERVING_PREFILL_CAP", "64"))
        if cap < 1 or cap & (cap - 1):
            raise ValueError(
                f"prefill_cap must be a power of two >= 1, got {cap} "
                "(the prefill ladder and the prefix-block ladder both "
                "key their bounded executable sets on it)")
        self.prefill_cap = cap
        # PAGED KV cache (default; PADDLE_SERVING_PAGED=0 keeps the
        # dense per-slot ring for parity testing): ONE BlockPool
        # [L, 2, NBtotal, H, Bt, D] shared by slots, prefixes, and
        # spec-verify writes, addressed through per-slot block tables
        # [B, Smax/Bt] that live here as pure data. Block size Bt IS
        # prefill_cap — the one knob. Slot capacity is bounded by the
        # POOL (actual token residency), not B x Smax; blocks map
        # lazily as lens grows and free on eviction. A shared dense
        # PrefixCache object forces dense mode (its pool is separate
        # storage). Under an active mp mesh the pool shards by HEAD on
        # the 'mp' axis (init_paged_cache lays it out with a
        # NamedSharding); the allocator, block tables and every
        # scheduler decision stay replicated host data, so paged mode
        # runs under a mesh with zero extra retraces — the only hard
        # requirement is num_heads % mp == 0.
        env_paged = os.environ.get("PADDLE_SERVING_PAGED", "1") != "0"
        want_paged = env_paged if paged is None else bool(paged)
        if want_paged and prefix_cache is not None:
            if paged:
                raise ValueError(
                    "a shared dense PrefixCache cannot back a paged "
                    "engine (its blocks live in separate storage; a "
                    "paged engine's prefix blocks ARE kv pool blocks) "
                    "— pass prefix_cache_blocks= instead, or "
                    "paged=False")
            want_paged = False
        _mesh = self.dec._mesh_mp()
        if want_paged and _mesh is not None:
            mp = dict(_mesh.shape)["mp"]
            nh_ = self.dec.fmt.num_heads
            if nh_ % mp:
                if paged:
                    # only the env/auto default may downgrade silently
                    # — an EXPLICIT paged=True must not quietly hand
                    # back a dense engine (fork_slot would then fail,
                    # the kv gate would never exist)
                    raise ValueError(
                        f"paged=True under an mp={mp} mesh needs "
                        f"num_heads % mp == 0 to shard the pool by "
                        f"head, got num_heads={nh_} — use a divisible "
                        "mesh degree or drop paged= to accept the "
                        "dense fallback")
                import warnings
                warnings.warn(
                    f"serving: paged KV pool disabled — num_heads="
                    f"{nh_} is not divisible by the mesh's mp degree "
                    f"{mp}, so the head-sharded pool layout is "
                    "unavailable; falling back to the dense ring",
                    RuntimeWarning, stacklevel=2)
                want_paged = False
        if _mesh is not None and self.dec._weight_shard_mesh() is None \
                and os.environ.get("PADDLE_SERVING_MESH_WEIGHTS",
                                   "1") != "0":
            # weight sharding wanted (mesh up, knob not opted out) but
            # the model axes don't divide mp: surface the replicated
            # downgrade at bring-up, not as a quiet HBM surprise
            import warnings
            mp = dict(_mesh.shape)["mp"]
            ff_ = int(self.dec.fmt.ffn1_weights[0]._data.shape[-1])
            warnings.warn(
                f"serving: weight sharding disabled — num_heads="
                f"{self.dec.fmt.num_heads} / ffn_dim={ff_} must both "
                f"divide the mesh's mp degree {mp} to shard the "
                "qkv/proj/FFN stacks; weights stay replicated per "
                "device (init_serving_mesh(mp, num_heads=, ffn_dim=) "
                "rejects this layout up front)",
                RuntimeWarning, stacklevel=2)
        self.paged = want_paged
        if weight_quant == "int4" and not self.paged:
            # explicit int4 is a serving-memory commitment: the dense
            # per-slot ring is the parity/bring-up layout (B x Smax HBM
            # regardless of residency), so pairing it with packed
            # weights states two contradictory memory intents — refuse
            # rather than ship a half-quantized engine silently. (The
            # env knob on a dense engine still works for parity runs;
            # only the EXPLICIT ctor pairing fails.)
            raise ValueError(
                "weight_quant='int4' with a dense KV ring: this engine "
                "resolved to the dense layout (PADDLE_SERVING_PAGED=0, "
                "paged=False, a shared dense prefix cache, or an "
                "indivisible head count under a mesh) — int4 packed "
                "weights are a paged-serving memory feature; use "
                "paged=True or drop weight_quant")
        if not self.paged and (kv_pool is not None
                               or kv_pool_blocks is not None):
            raise ValueError(
                "kv_pool/kv_pool_blocks state a paged-pool memory "
                "budget, but this engine resolved to the DENSE layout "
                "(PADDLE_SERVING_PAGED=0, paged=False, a shared dense "
                "prefix cache, or an indivisible head count under an "
                "active mp mesh) — refusing to drop the budget "
                "silently")
        self.pool = None
        self._kv_gate = False
        self._kv_reserved = 0            # running worst-case blocks
        self._kv_committed = 0           # queued + running worst case
        self._cow_copies = 0
        if self.paged:
            from .paged_kv import BlockPool
            nb_env = os.environ.get("PADDLE_SERVING_KV_BLOCKS")
            if kv_pool is not None:
                if kv_pool.block_tokens != cap:
                    raise ValueError(
                        f"BlockPool has block_tokens="
                        f"{kv_pool.block_tokens} but prefill_cap={cap} "
                        "— the pool block, the prefix block, and the "
                        "prefill chunk ladder are ONE knob and must "
                        "agree")
                if kv_pool.used:
                    # the engine owns the pool's DEVICE arrays; an
                    # allocator with live blocks belongs to another
                    # engine's storage (cross-engine pool sharing needs
                    # shared device buffers — not built yet)
                    raise ValueError(
                        "kv_pool already has allocated blocks — one "
                        "BlockPool serves one engine")
                self.pool = kv_pool
            else:
                nb = int(kv_pool_blocks if kv_pool_blocks is not None
                         else nb_env if nb_env
                         else self.num_slots * (self.smax // cap))
                self.pool = BlockPool(nb, cap, self.smax)
            # an EXPLICITLY sized pool is an operator-stated memory
            # budget: submit() sheds honestly (AdmissionFull) when
            # commitments exceed it. The default sizing (B x Smax/Bt ==
            # dense HBM) can always hold every admissible request, so
            # no gate — exact behavioral parity with the dense engine.
            self._kv_gate = (kv_pool is not None
                             or kv_pool_blocks is not None
                             or bool(nb_env))
        # automatic prefix caching: pass a shared PrefixCache (e.g. the
        # one oneshot generate() calls use) or a block budget to build a
        # private one; 0/None = off (legacy behavior, no new dispatches).
        # In paged mode the budget builds a PagedPrefixCache over the
        # SAME pool: adopt/commit become block-table index writes
        # (zero-copy hits) instead of compiled gather/splat copies.
        if prefix_cache is not None:
            from .prefix_cache import PrefixCache
            if not isinstance(prefix_cache, PrefixCache):
                # a PagedPrefixCache is engine-PRIVATE (its blocks live
                # in one engine's pool and tables) — accepting it here
                # would die later with an AttributeError in _admit
                raise ValueError(
                    f"prefix_cache= takes a shareable dense PrefixCache"
                    f", got {type(prefix_cache).__name__} — paged "
                    "engines build their own via prefix_cache_blocks=")
            if prefix_cache.block_tokens != self.prefill_cap:
                raise ValueError(
                    f"shared prefix cache has block_tokens="
                    f"{prefix_cache.block_tokens} but prefill_cap="
                    f"{self.prefill_cap} — the block and prefill ladders "
                    "must align")
            self.prefix_cache = prefix_cache
        elif prefix_cache_blocks:
            if self.paged:
                from .paged_kv import PagedPrefixCache
                self.prefix_cache = PagedPrefixCache(
                    int(prefix_cache_blocks), self.prefill_cap,
                    self.pool)
            else:
                from .prefix_cache import PrefixCache
                self.prefix_cache = PrefixCache(int(prefix_cache_blocks),
                                                self.prefill_cap)
        else:
            self.prefix_cache = None
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._pc_mesh_warned = False
        self._prefill_tokens_saved = 0
        self._prefill_tokens_computed = 0
        self._rep_on = bool(enable_repetition_penalty)
        self.clock = clock or time.perf_counter
        # telemetry subsystem (telemetry.py): per-request lifecycle
        # spans + the step timeline live in a bounded ring
        # (`telemetry_ring=` / PADDLE_TELEMETRY_RING, default 2048;
        # 0 disables collection — one branch per event, no timestamp
        # calls); the TTFT/latency/tokens-per-step histograms stay on
        # regardless (they are metrics()' percentile source and are
        # fixed-size). All timestamps ride the ENGINE clock, so spans
        # line up exactly with ttft_s/latency_s under a virtual clock.
        self.telemetry = Telemetry(telemetry_ring, clock=self.clock)
        # SLO/goodput layer (telemetry.SloPolicy; `slo=` or the
        # PADDLE_SLO_* knobs): every FINISHED request is classified at
        # _finish against the declared objectives — ok, violated by
        # queueing, or violated by slow service. With no objectives set
        # everything is ok, so slo_ok + slo_violated_queue +
        # slo_violated_service == requests_finished holds always (the
        # conftest reconciliation pins it).
        self._slo = slo if slo is not None else SloPolicy.from_env()
        self._slo_ok = 0
        self._slo_violated_queue = 0
        self._slo_violated_service = 0
        # results is BOUNDED at the telemetry ring size (the old
        # unbounded dict leaked one entry per finished request for the
        # engine's lifetime); total counts survive in the window
        # counters + the Prometheus lifetime base
        self._results_cap = self.telemetry.ring or DEFAULT_RING
        self._prom_base = {}          # lifetime counter base (reset folds)
        # speculative decoding: K draft tokens per verify step (ONE
        # compiled K+1-position executable replaces the decode chunk;
        # slots with no usable draft ride in all-masked and degrade to
        # a normal decode step). K is static trace structure — pow-2
        # validated like prefill_cap; 0 disables (legacy decode path).
        from .spec_decode import NGramDrafter, validate_spec_k
        self.spec_k = validate_spec_k(
            spec_k if spec_k is not None
            else os.environ.get("PADDLE_SERVING_SPEC_K", "0"))
        self._drafters = ([NGramDrafter(self.spec_k)
                           for _ in range(int(num_slots))]
                          if self.spec_k else None)
        # dispatch heuristic (PHASE mode only — DEPRECATED): a verify
        # step only beats `decode_chunk` plain steps when enough draft
        # tokens ride along to amortize its K+1-position pass — below
        # `spec_min_draft` average drafts per active slot the phase
        # engine runs the (equally warm) decode chunk instead. The
        # token-budget scheduler subsumes this with budget arithmetic
        # (drafts are just another claim on the step budget; the
        # dispatch that processes more real tokens wins), so in chunked
        # mode the env is ignored.
        self._spec_min_draft = float(os.environ.get(
            "PADDLE_SERVING_SPEC_MIN_DRAFT", "2"))
        self._spec_rng = None            # lazy: sampled-mode acceptance
        self._draft_proposed = 0
        self._draft_accepted = 0
        self._decode_steps = 0           # per-ROW sample events

        # TOKEN-BUDGET scheduler (default ON): every compiled step
        # spends `token_budget` tokens mixing decode rows (1 input
        # token + any draft claim each) with prefill chunks from
        # admitted-but-unprefilled slots — admission no longer runs a
        # blocking prefill phase, so one long prompt can't hold the
        # decode gang hostage (Sarathi-style chunked prefill).
        # token_budget=0 restores the legacy PHASE-prefill scheduler
        # (blocking bulk/scan prefill at admission) — kept as the A/B
        # baseline (tests/test_budget_scheduler.py holds token parity).
        # default: C = max(4 x decode_chunk, spec_k + 1) columns per
        # row — wide enough that a classic-length prompt (and a full
        # draft) lands in ONE dispatch; measured on the classic CPU
        # bench this beats the phase scheduler's bulk admission by
        # ~15% tokens/s where the ISSUE's leaner B x decode_chunk
        # (C = chunk) cost 15% (8 block steps per 32-token prompt)
        tb_env = os.environ.get("PADDLE_SERVING_TOKEN_BUDGET")
        tb = int(token_budget if token_budget is not None
                 else tb_env if tb_env
                 else self.num_slots * max(4 * self.decode_chunk,
                                           self.spec_k + 1))
        if tb < 0:
            raise ValueError(f"token_budget must be >= 0, got {tb}")
        if tb and tb < self.num_slots:
            raise ValueError(
                f"token_budget={tb} < num_slots={num_slots}: every "
                "active decode row claims one mandatory token per step, "
                "so the budget must cover at least the slot count "
                "(token_budget=0 disables chunked scheduling entirely)")
        self.token_budget = tb
        # the compiled budget step's column count C: per-row segment
        # cap, static shape. ceil(budget/B) rounds the shape to the
        # budget; a full draft (spec_k + the input token) must also fit
        # one row. pow-2 like every other ladder knob.
        cw = max(-(-tb // self.num_slots) if tb else 1, self.spec_k + 1)
        self._budget_cols = 1 << (cw - 1).bit_length()
        if tb and self.spec_k and \
                os.environ.get("PADDLE_SERVING_SPEC_MIN_DRAFT") is not None:
            import warnings
            warnings.warn(
                "PADDLE_SERVING_SPEC_MIN_DRAFT is deprecated and "
                "ignored under the token-budget scheduler (drafts are "
                "budget claims; the dispatch choice is budget "
                "arithmetic). Set token_budget=0 for the legacy phase "
                "scheduler if you need the old heuristic.",
                DeprecationWarning, stacklevel=2)
        # TOKEN-FLATTENED budget dispatch (PADDLE_SERVING_FLAT_BUDGET=1
        # / flat_budget=True; row-aligned stays the default until the
        # bench A/B gate flips it): the budget step becomes ONE ragged
        # [T] token stream — a B-wide decode region plus back-to-back
        # segments with eighth-octave ladder width — instead of the [B, C]
        # block, so T real tokens cost ~T computed positions
        # (budget_padding_tokens ~ 0) where the row layout paid B x C
        # (a lone long prefill wasted (B-1) x C per dispatch), and one
        # prefill segment can span the whole spare budget instead of C
        # columns. Token parity with the row layout is exact (greedy
        # AND sampled — sampling is keyed fold_in(seed, nt), never by
        # layout); tests/test_flat_budget.py pins it.
        flat_env = os.environ.get("PADDLE_SERVING_FLAT_BUDGET", "0")
        self._flat_budget = (bool(flat_budget)
                             if flat_budget is not None
                             else flat_env == "1")
        if self._flat_budget and not tb:
            raise ValueError(
                "flat_budget needs the token-budget scheduler "
                "(token_budget > 0): the flat [T] stream IS the budget "
                "dispatch — token_budget=0 selects the legacy phase "
                "scheduler, which has no budget step to flatten")
        # prefill progress: prompt tokens still to feed per slot (> 0
        # marks an admitted-but-unprefilled "prefilling" slot the
        # budget packer advances, oldest request first)
        self._pf_left = np.zeros(int(num_slots), np.int64)
        self._budget_steps = 0
        self._budget_tokens_used = 0
        self._budget_prefill_tokens = 0
        self._budget_decode_tokens = 0
        self._budget_draft_tokens = 0
        # masked/pad positions the budget dispatches actually computed
        # (row: B x C - packed; flat: (B + T_seg) - packed) — the
        # wasted-FLOPs ledger the flat layout exists to flatten;
        # utilization = used / (used + padding) by construction
        self._budget_padding_tokens = 0

        b = self.num_slots
        fmt.eval()
        if self.paged:
            self._caches = self.dec.init_paged_cache(self.pool)
            # per-slot block tables: position s of slot b lives at
            # pool[.., tables[b, s // Bt], .., s % Bt, ..]; the sentinel
            # num_blocks marks unmapped entries (writes through it drop)
            self._tables = np.full((b, self.smax // self.prefill_cap),
                                   self.pool.num_blocks, np.int32)
        else:
            self._caches = self.dec.init_cache(b)
            self._tables = None
        # host-side slot state (tiny [B] vectors; device arrays would buy
        # nothing — they cross the boundary once per chunk anyway)
        self._lens = np.zeros(b, np.int32)       # current decode position
        self._active = np.zeros(b, bool)
        self._nt = np.zeros(b, np.int32)         # tokens generated so far
        self._max_nt = np.ones(b, np.int32)
        self._eos = np.full(b, -1, np.int32)     # -1: no eos for the slot
        self._min_len = np.zeros(b, np.int32)
        self._rep_pen = np.ones(b, np.float32)
        self._tok = np.zeros(b, np.int32)        # next step's input token
        self._rseed = np.zeros(b, np.int64)      # per-request sample seed
        self._slot_req = [None] * b              # slot -> ServedRequest
        self._presence = None                    # [B, V] bool when rep_on

        # live-migration counters: a migrated-in request enters a slot
        # WITHOUT an admission (no prefix lookup, no prefill — its KV
        # arrived as pool blocks), a migrated-out one leaves without a
        # finish verdict; both are first-class window counters so the
        # conftest reconciliations stay exact
        self._migrated_in = 0
        self._migrated_out = 0
        # disaggregated-handoff counters + staging area: shipped counts
        # every KV block serialized OFF this engine (export_slot and
        # the streamed export_kv_prefix), adopted every block written
        # INTO this engine's pool from a shipped payload (import_slot
        # uploads and stage_kv_blocks). Cluster-wide, lossless handoff
        # conserves sum(shipped) == sum(adopted); preemption-to-host
        # serializes inline and never touches either. _staged maps a
        # router-chosen tag -> pool block ids received AHEAD of the
        # final export (streamed handoff overlapping the prefill tail)
        self._kv_blocks_shipped = 0
        self._kv_blocks_adopted = 0
        self._staged = {}

        # QoS: one FIFO per class, admitted best-class-first (all-default
        # workloads collapse to the old single FIFO, token-identically);
        # the parking lot holds preempted slot state dicts (host RAM —
        # export_slot already serializes everything, kv blocks included)
        self._queues = {c: deque() for c in QOS_CLASSES}
        self._parked = {}                 # rid -> export_slot state dict
        self._preempted = 0
        self._resumed = 0
        self._class_admitted = {c: 0 for c in QOS_CLASSES}
        self._class_tokens = {c: 0 for c in QOS_CLASSES}
        self._slo_vq_class = {c: 0 for c in QOS_CLASSES}
        # weighted-fair prefill shares (host data only — the packer
        # changes WHICH rows fill the same fixed-shape budget, never the
        # shapes, so zero retraces by construction)
        self.qos_shares = self._parse_qos_shares(
            os.environ.get("PADDLE_QOS_SHARES", ""))
        self.results = {}
        # streaming-harvest bookkeeping: every queued/running request is
        # reachable by rid (bounded by queue + slots); a FINISHED request
        # stays indexed only while a track() cursor holds it — the
        # incremental SSE reader's guarantee against the results cap
        self._req_index = {}              # rid -> ServedRequest
        self._harvest = {}                # rid -> tokens already read
        self._rid = itertools.count()
        self._jit_cache = {}
        self._trace_count = 0                    # the retrace spy
        # per-chunk metric records, bounded: a server driving step()
        # forever must not leak one dict per chunk (metrics() reads the
        # aggregate counters, never this log — it is observability only)
        self.chunk_log = deque(maxlen=int(os.environ.get(
            "PADDLE_TPU_SERVE_CHUNK_LOG", "4096")))
        self._tokens_emitted = 0
        self._busy_s = 0.0
        # EWMA of WORKING step duration (snapshot v6 "health" block):
        # the replica-local slowness signal the cluster router's
        # median-relative health scorer compares across replicas
        self._step_ewma_s = 0.0
        self._admitted = 0
        self._forked = 0
        # window counter (was recomputed from the results dict, which is
        # bounded now — an unbounded scan AND an unbounded dict at
        # service lifetimes); expired requests never count here
        self._finished = 0
        # overload shedding: 0 = unbounded (legacy behavior)
        self.max_pending = int(max_pending if max_pending is not None
                               else os.environ.get(
                                   "PADDLE_TPU_SERVE_MAX_PENDING", "0"))
        self._rejected = 0
        self._expired = 0

    # ------------------------------------------------------------- public
    def submit(self, prompt, max_new_tokens=20, eos_token_id=None,
               min_length=0, repetition_penalty=1.0, deadline_s=None,
               trace_id=None, attempt=1, priority=QOS_DEFAULT):
        """Queue one request; returns its id. The slot-eviction invariant
        is enforced HERE: a request may never be able to push its slot's
        cache_lens to Smax (the write kernels' documented invariant).
        prompt + max_new_tokens == Smax is allowed: cache_lens peaks at
        Smax - 1, because a slot that deactivates (nt hit
        max_new_tokens) stops INCREMENTING lens. The decode scan still
        runs unmasked for inactive rows (a write mask would demote the
        fused write+attend kernel), so the last sampled token's K/V IS
        written — at the frozen lens == Smax - 1, rewritten with the
        same value each subsequent chunk while the slot idles. In-bounds
        by the check below, overwritten by the next admission's prefill;
        do NOT snapshot a finished slot's cache row expecting it frozen
        as of the final active step."""
        ids = prompt._data if isinstance(prompt, Tensor) else prompt
        ids = np.asarray(ids, np.int32).reshape(-1)
        if ids.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if ids.size + int(max_new_tokens) > self.smax:
            raise ValueError(
                f"prompt ({ids.size}) + max_new_tokens ({max_new_tokens})"
                f" exceeds the ring capacity Smax={self.smax} — the slot "
                "could fill its cache row (cache_lens < Smax invariant)")
        if repetition_penalty != 1.0 and not self._rep_on:
            raise ValueError(
                "repetition_penalty needs enable_repetition_penalty=True "
                "at engine construction (the presence-mask carry is "
                "static trace structure)")
        if attempt < 1:
            raise ValueError(f"attempt must be >= 1, got {attempt}")
        if priority not in QOS_CLASSES:
            raise ValueError(
                f"priority must be one of {QOS_CLASSES}, got {priority!r}")
        if self.max_pending and self._queue_len() >= self.max_pending:
            self._rejected += 1
            if self.telemetry.enabled:
                self.telemetry.req_rejected(self.clock(),
                                            trace_id=trace_id,
                                            attempt=attempt)
            raise AdmissionFull(
                f"pending queue full ({self._queue_len()}/"
                f"{self.max_pending}) — request shed at admission")
        if self.paged:
            need = self._blocks_needed(ids.size, max_new_tokens)
            if need > self.pool.num_blocks:
                raise ValueError(
                    f"request needs {need} kv blocks but the pool holds "
                    f"{self.pool.num_blocks} total — it can never be "
                    "admitted (grow kv_pool_blocks or shrink the "
                    "request)")
            if self._kv_gate and \
                    self._kv_committed + need > self.pool.num_blocks:
                # the POOL (not the slot count) is exhausted: honest
                # shedding against the operator's stated memory budget
                # — finished/expired requests release their commitment,
                # so the caller's backoff-and-retry recovers
                self._rejected += 1
                if self.telemetry.enabled:
                    self.telemetry.req_rejected(self.clock(),
                                                trace_id=trace_id,
                                                attempt=attempt)
                raise AdmissionFull(
                    f"kv pool exhausted ({self._kv_committed}/"
                    f"{self.pool.num_blocks} blocks committed to "
                    f"queued+running requests; this one needs {need}) "
                    "— request shed at admission")
            self._kv_committed += need
        req = ServedRequest(next(self._rid), ids, max_new_tokens,
                            eos_token_id, min_length, repetition_penalty,
                            self.clock(), deadline_s=deadline_s,
                            seed=self._fresh_seed(), trace_id=trace_id,
                            attempt=attempt, priority=priority)
        self._queues[priority].append(req)
        self._req_index[req.rid] = req
        self.telemetry.req_queued(req.rid, req.t_submit,
                                  trace_id=req.trace_id,
                                  attempt=req.attempt)
        return req.rid

    def _fresh_seed(self):
        """One per-request sampling seed off the global key stream
        (greedy engines skip the draw: submit order then can't perturb
        unrelated consumers of the global key)."""
        return _host_seed(next_key()) if self.do_sample else 0

    # ------------------------------------------------- per-class queues
    # The admission order is strict priority across classes (best class
    # first), FIFO within a class — these four helpers are the ONLY code
    # that touches the per-class deques, so the old single-FIFO call
    # sites read unchanged.
    @staticmethod
    def _parse_qos_shares(spec):
        """Parse ``high=4,normal=2,low=1`` into a share dict; unknown
        classes reject loudly, missing ones keep the default weight."""
        shares = dict(DEFAULT_QOS_SHARES)
        for part in str(spec).split(","):
            part = part.strip()
            if not part:
                continue
            cls, _, w = part.partition("=")
            if cls not in QOS_CLASSES:
                raise ValueError(
                    f"PADDLE_QOS_SHARES: unknown class {cls!r} "
                    f"(classes: {QOS_CLASSES})")
            w = int(w)
            if w < 1:
                raise ValueError(
                    f"PADDLE_QOS_SHARES: share for {cls!r} must be "
                    f">= 1, got {w}")
            shares[cls] = w
        return shares

    def _queue_len(self):
        return sum(len(q) for q in self._queues.values())

    def _queue_head(self):
        for c in QOS_CLASSES:
            if self._queues[c]:
                return self._queues[c][0]
        return None

    def _queue_popleft(self):
        for c in QOS_CLASSES:
            if self._queues[c]:
                return self._queues[c].popleft()
        raise IndexError("pop from empty queue")

    def _queue_remove(self, req):
        self._queues[req.priority].remove(req)

    def queue_depths(self):
        """Per-class pending depths (host dict; snapshot v4 surface)."""
        return {c: len(self._queues[c]) for c in QOS_CLASSES}

    @property
    def has_work(self):
        return (bool(self._queue_len()) or bool(self._active.any())
                or bool((self._pf_left > 0).any())
                or bool(self._parked))

    @property
    def queue_depth(self):
        return self._queue_len()

    @property
    def occupancy(self):
        if not self.num_slots:
            return 0.0
        # a slot mid-prefill is occupied even though it isn't decoding
        return float((self._active | (self._pf_left > 0)).mean())

    @no_grad()
    def step(self):
        """One scheduler iteration. Token-budget mode (default): admit
        waiting requests into free slots as PURE BOOKKEEPING (they
        enter `prefilling` — no blocking prefill phase), then run one
        budget-packed dispatch mixing decode rows and prefill chunks.
        Phase mode (token_budget=0): the legacy blocking-prefill
        admission + decode chunk. Emits one chunk_log record; returns
        the number of tokens emitted this step."""
        t0 = self.clock()
        # gray-failure chaos hook: PADDLE_FI_SLOW_POINT=serve_step slows
        # THIS engine's scheduler loop (per-process env = per-replica in
        # an rpc cluster) while its heartbeat keeps beating — the
        # router's health scoring, not death detection, must notice.
        # After t0, so the injected delay lands in the step-duration
        # EWMA the snapshot health block reports. Free when disarmed
        # (inject() gates on any PADDLE_FI_* set).
        from ..testing import fault
        fault.inject("serve_step")
        had_work = self.has_work
        self._expire_deadlines(t0)
        # QoS pass BEFORE admission: resume parked requests when pressure
        # cleared, preempt the lowest-class running slot when a better-
        # class head is blocked — so this step's admission sees the slot
        self._qos_schedule()
        if self.token_budget:
            self._admit_chunked()
            emitted = self._budget_step()
        else:
            admitted = self._admit()
            emitted = len(admitted)
            # phase-mode hold runs BETWEEN admission (which already
            # sampled the first token) and the decode chunk — a
            # prefill worker must never spend a decode dispatch on a
            # request that is about to ship out
            if self.role == "prefill":
                self._hold_prefilled()
            if self._active.any():
                emitted += (self._spec_decode_step() if self.spec_k
                            else self._decode_one_chunk())
        if self.role == "prefill":
            # budget-mode hold: a slot whose prompt completed in this
            # dispatch (first token sampled, decoding would start next
            # step) parks as "prefilled" awaiting the KV handoff
            self._hold_prefilled()
        # re-check AFTER the dispatch: a deadline that lapsed while the
        # step ran (or while admission waits on a head-of-line block
        # reservation) must expire now, not one full step later — a
        # queued request behind a pool-exhausted admission otherwise
        # sits past its deadline for a whole extra dispatch
        self._expire_deadlines(self.clock())
        dt = self.clock() - t0
        self._busy_s += dt
        self._tokens_emitted += emitted
        if had_work:
            # smoothed WORKING-step duration (idle steps would dilute
            # the gray-failure signal toward zero on a lulled replica)
            self._step_ewma_s = (dt if self._step_ewma_s == 0.0
                                 else 0.8 * self._step_ewma_s + 0.2 * dt)
            # tokens-per-step distribution (0 is a real value: a pure-
            # prefill budget step emits nothing and that IS the story)
            self.telemetry.observe_step_tokens(emitted)
        self.chunk_log.append({
            "step_s": dt, "new_tokens": emitted,
            "occupancy": self.occupancy, "queue_depth": self.queue_depth,
            "traces": self._traces_total(),
        })
        return emitted

    def run(self):
        """Drive until the queue and all slots drain."""
        while self.has_work:
            self.step()
        return self.results

    def _hold_prefilled(self):
        """Role "prefill" only: park every slot whose prompt finished
        (first token sampled, decode would start next dispatch) as
        state ``prefilled`` — active=False, KV blocks and slot stay
        RESIDENT awaiting export_slot to a decode replica. The request
        rides the streaming harvest as (tokens, done=False,
        "prefilled"), which is the router's handoff trigger. Requests
        that finished ON their first token (eos / max_new_tokens == 1)
        were already completed by the dispatch harvest and never reach
        here. A held slot drops out of ``has_work`` on purpose: the
        prefill worker idles (or admits the next prompt into other
        slots) while the router drives the transfer."""
        for s in range(self.num_slots):
            req = self._slot_req[s]
            if (req is not None and req.state == "running"
                    and self._active[s] and not self._pf_left[s]
                    and self._nt[s] >= 1):
                req.state = "prefilled"
                self._active[s] = False
                if self.telemetry.enabled:
                    self.telemetry.req_event(req.rid, "prefill_hold",
                                             self.clock())

    # ------------------------------------------------- streaming harvest
    def _lookup_req(self, rid):
        """(tokens, done, state) for a rid, or None if unknown: live
        requests read their ServedRequest, finished untracked ones fall
        back to the bounded results record."""
        req = self._req_index.get(rid)
        if req is not None:
            return (req.tokens, req.state in ("finished", "expired"),
                    req.state)
        r = self.results.get(rid)
        if r is not None:
            return (r["tokens"], True,
                    "expired" if r["expired"] else "finished")
        return None

    def track(self, rid):
        """Register an incremental-harvest cursor for ``rid``. A tracked
        request's record is retained past the bounded ``results`` cap
        until the reader drains it — call BEFORE the request can finish
        (the replica wrappers do it under the same lock as submit) or
        the registration races the cap like any late ``results`` read."""
        if rid in self._harvest:
            return
        if self._lookup_req(rid) is None:
            raise KeyError(
                f"request {rid} is unknown (never submitted, or it "
                "finished and was evicted from the bounded results cap "
                "before track() — register the cursor at submit time)")
        self._harvest[rid] = 0

    def poll(self, rid):
        """Non-destructive status read: ``{"rid", "state", "n_tokens",
        "ttft_s", "latency_s"}``, or None for an unknown rid. Moves no
        cursor and no counter — safe to call at any rate."""
        req = self._req_index.get(rid)
        if req is not None:
            return {"rid": rid, "state": req.state,
                    "n_tokens": len(req.tokens), "ttft_s": req.ttft_s,
                    "latency_s": req.latency_s}
        r = self.results.get(rid)
        if r is None:
            return None
        return {"rid": rid,
                "state": "expired" if r["expired"] else "finished",
                "n_tokens": int(np.asarray(r["tokens"]).size),
                "ttft_s": r["ttft_s"], "latency_s": r["latency_s"]}

    def harvest_new_tokens(self, rid):
        """Incremental token harvest: ``(new_tokens, done, state)`` —
        the tokens generated since the previous call (first call
        auto-registers a cursor at 0 and returns everything so far).
        When ``done`` the cursor is dropped and the retained record
        released; a later call raises KeyError like any unknown rid.
        This is the SSE streaming primitive: a tracked reader can be
        arbitrarily slow without losing a finished request to the
        results cap (the untracked `results` dict can — documented)."""
        if rid not in self._harvest:
            self.track(rid)
        got = self._lookup_req(rid)
        if got is None:                  # evicted between track and now:
            self._harvest.pop(rid, None)  # only possible for a cursor
            raise KeyError(              # registered post-finish
                f"request {rid} was evicted from the results cap before "
                "its first harvest — track() at submit time to pin it")
        tokens, done, state = got
        cur = self._harvest[rid]
        new = [int(t) for t in tokens[cur:]]
        if done:
            self.release(rid)
        else:
            self._harvest[rid] = cur + len(new)
        return new, done, state

    def release(self, rid):
        """Drop a streaming cursor (and the retained record, if the
        request already finished). Idempotent."""
        self._harvest.pop(rid, None)
        req = self._req_index.get(rid)
        if req is not None and req.state in ("finished", "expired"):
            self._req_index.pop(rid, None)

    def _window_counters(self):
        """The raw window-counter surface, keyed like metrics(). Kept in
        ONE place so reset_metrics' lifetime-base folding (Prometheus
        counters must be monotonic across resets) can assert it covers
        exactly telemetry.COUNTER_FOLD_KEYS — a new counter that skips
        either side fails loudly here, not silently in a dashboard."""
        return {
            "tokens_emitted": self._tokens_emitted,
            "busy_s": self._busy_s,
            "requests_finished": self._finished,
            "requests_admitted": self._admitted,
            "requests_forked": self._forked,
            "requests_rejected": self._rejected,
            "requests_expired": self._expired,
            "requests_migrated_in": self._migrated_in,
            "requests_migrated_out": self._migrated_out,
            "kv_blocks_shipped": self._kv_blocks_shipped,
            "kv_blocks_adopted": self._kv_blocks_adopted,
            "requests_preempted": self._preempted,
            "requests_resumed": self._resumed,
            "requests_admitted_high": self._class_admitted["high"],
            "requests_admitted_normal": self._class_admitted["normal"],
            "requests_admitted_low": self._class_admitted["low"],
            "tokens_emitted_high": self._class_tokens["high"],
            "tokens_emitted_normal": self._class_tokens["normal"],
            "tokens_emitted_low": self._class_tokens["low"],
            "prefix_hits": self._prefix_hits,
            "prefix_misses": self._prefix_misses,
            "prefill_tokens_saved": self._prefill_tokens_saved,
            "prefill_tokens_computed": self._prefill_tokens_computed,
            "decode_steps": self._decode_steps,
            "draft_proposed": self._draft_proposed,
            "draft_accepted": self._draft_accepted,
            "kv_cow_copies": self._cow_copies,
            "budget_steps": self._budget_steps,
            "budget_tokens_used": self._budget_tokens_used,
            "budget_prefill_tokens": self._budget_prefill_tokens,
            "budget_decode_tokens": self._budget_decode_tokens,
            "budget_draft_tokens": self._budget_draft_tokens,
            "budget_padding_tokens": self._budget_padding_tokens,
            "slo_ok": self._slo_ok,
            "slo_violated_queue": self._slo_violated_queue,
            "slo_violated_service": self._slo_violated_service,
        }

    def reset_metrics(self, keep_results=True):
        """Zero the aggregate counters (benchmarks call this after a
        warmup phase so the measured window excludes compiles). The
        trace counter is NOT reset — retraces-after-warmup is exactly
        `metrics()['traces']` before vs after the measured phase.
        Every window counter folds into the Prometheus lifetime base
        first (metrics_prometheus() counters never move backwards), and
        the telemetry rings/histograms start a fresh window (the next
        export_chrome_tracing covers exactly the measured window)."""
        window = self._window_counters()
        assert set(window) == set(COUNTER_FOLD_KEYS), (
            "window-counter surface drifted from telemetry."
            "COUNTER_FOLD_KEYS: "
            f"{set(window) ^ set(COUNTER_FOLD_KEYS)}")
        for k, v in window.items():
            self._prom_base[k] = self._prom_base.get(k, 0) + v
        self.telemetry.reset()
        self.chunk_log.clear()
        self._tokens_emitted = 0
        self._busy_s = 0.0
        self._admitted = 0
        self._forked = 0
        self._finished = 0
        self._rejected = 0
        self._expired = 0
        self._migrated_in = 0
        self._migrated_out = 0
        self._kv_blocks_shipped = 0
        self._kv_blocks_adopted = 0
        self._preempted = 0
        self._resumed = 0
        self._class_admitted = {c: 0 for c in QOS_CLASSES}
        self._class_tokens = {c: 0 for c in QOS_CLASSES}
        self._slo_vq_class = {c: 0 for c in QOS_CLASSES}
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefill_tokens_saved = 0
        self._prefill_tokens_computed = 0
        self._draft_proposed = 0
        self._draft_accepted = 0
        self._decode_steps = 0
        self._cow_copies = 0
        self._budget_steps = 0
        self._budget_tokens_used = 0
        self._budget_prefill_tokens = 0
        self._budget_decode_tokens = 0
        self._budget_draft_tokens = 0
        self._budget_padding_tokens = 0
        self._slo_ok = 0
        self._slo_violated_queue = 0
        self._slo_violated_service = 0
        if not keep_results:
            self.results = {}

    def metrics(self):
        # percentiles come from the telemetry subsystem's BOUNDED
        # log-bucketed histograms (estimates within one bucket width of
        # exact), not a scan over per-request records: the old
        # done-list walk grew without bound at service lifetimes, and
        # the results dict it walked is capped now. Expired requests
        # are SHED, not finished — they never reach the histograms
        # (their "latency" is an eviction time) and never count in
        # requests_finished (else finished + expired double-counts).
        tele = self.telemetry
        looked = self._prefix_hits + self._prefix_misses
        _w_dev, _w_repl = self._weight_bytes()
        m = {
            "tokens_emitted": self._tokens_emitted,
            "busy_s": round(self._busy_s, 4),
            # zero-elapsed guard: a frozen/coarse clock can leave
            # busy_s == 0.0 with tokens already emitted (first-step
            # metrics call) — report a throughput of 0.0, never divide
            "tokens_per_sec": (
                round(self._tokens_emitted / self._busy_s, 2)
                if self._busy_s > 0
                else (0.0 if self._tokens_emitted else None)),
            "requests_finished": self._finished,
            "requests_admitted": self._admitted,
            "requests_forked": self._forked,
            "requests_rejected": self._rejected,
            "requests_expired": self._expired,
            # live-migration window counters (0 unless a cluster drain
            # moved sessions): migrated_in entered a slot with KV blocks
            # shipped from another engine (no admission, no prefill);
            # migrated_out left mid-flight with their state
            "requests_migrated_in": self._migrated_in,
            "requests_migrated_out": self._migrated_out,
            # disaggregation surface: the engine's pool role (static
            # config — "mixed" runs today's combined behavior) plus the
            # KV-handoff window counters. Shipped counts blocks this
            # engine read out for another engine (export_slot payloads
            # + streamed export_kv_prefix chunks); adopted counts
            # blocks written INTO this pool from another engine
            # (import_slot payloads + stage_kv_blocks uploads).
            "role": self.role,
            "kv_blocks_shipped": self._kv_blocks_shipped,
            "kv_blocks_adopted": self._kv_blocks_adopted,
            # QoS window counters: preempted running slots parked to
            # host RAM, resumed re-imported; parked is a live gauge.
            # Per-class admissions/tokens sum to the totals (all-default
            # traffic lands entirely in "normal") — conftest pins it.
            "requests_preempted": self._preempted,
            "requests_resumed": self._resumed,
            "requests_parked": len(self._parked),
            "requests_admitted_high": self._class_admitted["high"],
            "requests_admitted_normal": self._class_admitted["normal"],
            "requests_admitted_low": self._class_admitted["low"],
            "tokens_emitted_high": self._class_tokens["high"],
            "tokens_emitted_normal": self._class_tokens["normal"],
            "tokens_emitted_low": self._class_tokens["low"],
            "queue_depth": self.queue_depth,
            "occupancy": self.occupancy,
            "traces": self._traces_total(),
            "ttft_p50_s": tele.hist_ttft.percentile(50),
            "ttft_p90_s": tele.hist_ttft.percentile(90),
            "ttft_p99_s": tele.hist_ttft.percentile(99),
            "latency_p50_s": tele.hist_latency.percentile(50),
            "latency_p99_s": tele.hist_latency.percentile(99),
            # prefix-cache window counters (all zero with caching off):
            # hits + misses == requests_admitted by construction; saved +
            # computed == total prompt tokens admitted this window
            "prefix_hits": self._prefix_hits,
            "prefix_misses": self._prefix_misses,
            "prefix_hit_rate": (round(self._prefix_hits / looked, 4)
                                if looked else None),
            "prefill_tokens_saved": self._prefill_tokens_saved,
            "prefill_tokens_computed": self._prefill_tokens_computed,
            # speculative-decoding window counters (spec_k=0 keeps
            # proposed/accepted at 0 and tokens_per_step at exactly 1):
            # decode_steps counts per-ROW sample events (the admit
            # first-token sample + each decode/verify row-step), so
            # tokens_emitted == decode_steps + draft_accepted always —
            # the conftest reconciliation pins it
            "decode_steps": self._decode_steps,
            "draft_proposed": self._draft_proposed,
            "draft_accepted": self._draft_accepted,
            "acceptance_rate": (
                round(self._draft_accepted / self._draft_proposed, 4)
                if self._draft_proposed else None),
            "tokens_per_step": (
                round(self._tokens_emitted / self._decode_steps, 4)
                if self._decode_steps else None),
            # paged-pool accounting (dense mode: total/used/free None):
            # used + free == total always — a refcounted block shared
            # by N slots and the prefix store is ONE physical block,
            # counted once. kv_cow_copies is a window counter (0 in
            # the steady flow; forks pay one per diverged block).
            "kv_blocks_total": (self.pool.num_blocks if self.paged
                                else None),
            "kv_blocks_used": self.pool.used if self.paged else None,
            "kv_blocks_free": (self.pool.free_count if self.paged
                               else None),
            "kv_cow_copies": self._cow_copies,
            # mesh-sharded pool layout gauges (static config, so they
            # survive reset_metrics unchanged without an exemption;
            # dense mode: all None): shard_count is the mesh's mp
            # degree (1 when a paged engine runs unsharded),
            # shard_heads the per-device head count, and
            # shard_pool_bytes the PER-DEVICE kv(+scales) bytes —
            # shard_count x shard_pool_bytes == the full pool, i.e.
            # per-device residency is dense/mp
            "kv_shard_count": self._kv_shard_count(),
            "kv_shard_heads": self._kv_shard_heads(),
            "kv_shard_pool_bytes": self._kv_shard_pool_bytes(),
            # tensor-parallel WEIGHT placement gauges (static config,
            # reset-stable like the kv_shard trio, but never None —
            # every engine has weights): shard_count is the weight-
            # shard mp degree (1 = replicated / no mesh),
            # weight_bytes_per_device the per-chip bytes of the exact
            # arrays the step dispatches (stacked layer pytree + embed
            # + LM head, int8 mirrors at their quantized size), and
            # weight_bytes_replicated the per-chip share that stays
            # replicated (LN/bias/scale mirrors, embed, an indivisible
            # LM head). The identity
            #   (per_device - replicated) * shard_count + replicated
            #     == dense total bytes
            # holds exactly on every engine (conftest pins it).
            "weight_shard_count": self._weight_shard_count(),
            "weight_bytes_per_device": _w_dev,
            "weight_bytes_replicated": _w_repl,
            # token-budget window counters (all zero in phase mode):
            # used = the REAL tokens packed into budget dispatches
            # (prefill + decode + draft parts sum to it exactly — the
            # conftest reconciliation pins the split); padding = the
            # masked/pad positions those dispatches actually COMPUTED
            # (row-aligned: B x C - used per step; flat: the decode
            # region's idle rows + alignment/ladder tail — the flat
            # layout's whole point is driving this to ~0). Utilization
            # is used / (used + padding): the denominator is each
            # dispatch's real compute width (B x C row-aligned, T
            # flat), so the gauge stays in (0, 1] under BOTH layouts.
            # Plain decode-chunk dispatches the budget arithmetic
            # falls back to are NOT budget steps and don't count here.
            "budget_steps": self._budget_steps,
            "budget_tokens_used": self._budget_tokens_used,
            "budget_prefill_tokens": self._budget_prefill_tokens,
            "budget_decode_tokens": self._budget_decode_tokens,
            "budget_draft_tokens": self._budget_draft_tokens,
            "budget_padding_tokens": self._budget_padding_tokens,
            "budget_utilization": (
                round(self._budget_tokens_used
                      / (self._budget_tokens_used
                         + self._budget_padding_tokens), 4)
                if self._budget_steps and self._budget_tokens_used
                else None),
            # SLO/goodput window counters (SloPolicy; objectives unset
            # = everything ok): ok + violated_queue + violated_service
            # == requests_finished by construction — every finished
            # request gets exactly one verdict at _finish
            "slo_ok": self._slo_ok,
            "slo_violated_queue": self._slo_violated_queue,
            "slo_violated_service": self._slo_violated_service,
            # queue-wait vs service-time decomposition percentiles
            # (the cause-attribution signal, same bounded histograms
            # discipline as ttft/latency)
            "queue_p50_s": tele.hist_queue.percentile(50),
            "queue_p99_s": tele.hist_queue.percentile(99),
            "service_p50_s": tele.hist_service.percentile(50),
            "service_p99_s": tele.hist_service.percentile(99),
        }
        if self.prefix_cache is not None:
            m["prefix_store"] = self.prefix_cache.store.stats()
        return m

    def _kv_shard_count(self):
        """Number of pool shards: the mesh's mp degree, 1 for an
        unsharded paged engine, None in dense mode (no pool)."""
        if not self.paged:
            return None
        mesh = self.dec._mesh_mp()
        return dict(mesh.shape)["mp"] if mesh is not None else 1

    def _kv_shard_heads(self):
        n = self._kv_shard_count()
        return None if n is None else self.dec.fmt.num_heads // n

    def _kv_shard_pool_bytes(self):
        """Per-device pool residency: kv(+scales) bytes / shard count.
        The head axis divides exactly (enforced at construction), so
        this is the precise per-chip HBM the pool costs — dense/mp."""
        n = self._kv_shard_count()
        if n is None:
            return None
        total = int(self._caches["kv"].nbytes)
        if "sc" in self._caches:
            total += int(self._caches["sc"].nbytes)
        return total // n

    def _weight_arrays(self):
        """The EXACT device arrays the serving step dispatches with:
        the stacked layer pytree, the embedding params, and the
        (possibly quantized / vocab-sharded) LM-head arrays. One list
        so the weight gauges and the conftest identity reconciliation
        account the same bytes."""
        dec = self.dec
        arrs = list(dec._stacked().values())
        arrs += [p._data for p in dec._embed_params]
        arrs += list(dec._maybe_quant_head(
            [p._data for p in dec._head_params]))
        return arrs

    def _weight_shard_count(self):
        """Weight-shard degree: the mesh's mp when the stacks shard,
        1 when weights are replicated (no mesh, opt-out, or an
        indivisible head/FFN axis)."""
        mesh = self.dec._weight_shard_mesh()
        return dict(mesh.shape)["mp"] if mesh is not None else 1

    def _weight_bytes(self):
        """(per_device, replicated) weight bytes. per_device sums each
        array's LOCAL shard footprint (sharding.shard_shape — the full
        shape for replicated arrays, shape/mp on the sharded axis
        otherwise); replicated sums only the arrays whose local shard
        IS the full array. With n = _weight_shard_count(),
        (per_device - replicated) * n + replicated recovers the dense
        byte total exactly."""
        import math
        per_dev = repl = 0
        for a in self._weight_arrays():
            shape = tuple(a.shape)
            shard = tuple(a.sharding.shard_shape(shape)) if hasattr(
                a, "sharding") else shape
            b = math.prod(shard) * a.dtype.itemsize
            per_dev += b
            if shard == shape:
                repl += b
        return per_dev, repl

    def metrics_prometheus(self):
        """Prometheus text-format exposition: every metrics() key under
        a stable name (telemetry.PROMETHEUS_NAMES), counters monotonic
        across reset_metrics (lifetime base + window), the bounded
        TTFT/latency/tokens-per-step histograms, pool/prefix gauges,
        and the distributed-runtime section (watchdog heartbeat ages,
        supervisor generation, rpc latency)."""
        from .telemetry import render_prometheus
        return render_prometheus(self)

    def telemetry_snapshot(self):
        """JSON-serializable state snapshot — the routing payload a
        cluster front-end polls per replica (queue depth + occupancy +
        pool headroom + histogram percentiles in one cheap read)."""
        from .telemetry import snapshot
        return snapshot(self)

    def _traces_total(self):
        """Engine traces + the prefix cache's copy-path traces: the
        zero-retrace-after-warmup contract covers the adopt/commit
        executables too (a shared PrefixCache may also accrue traces
        from oneshot generate() calls — still honest: any trace hits
        the same compile stall)."""
        n = self._trace_count
        if self.prefix_cache is not None:
            n += self.prefix_cache.trace_count
        if self.pool is not None:
            n += self.pool.trace_count       # the COW copy executable
        return n

    # ------------------------------------------------------- jitted steps
    def _counted_jit(self, key, build, donate=()):
        """jit with a retrace spy (paged_kv.counted_jit is the one
        owner): the counter bumps at TRACE time, so
        `metrics()['traces']` counts executable builds, not calls — the
        engine's zero-retrace-after-warmup contract is asserted against
        exactly this number."""
        from .paged_kv import counted_jit
        return counted_jit(self._jit_cache, key, build,
                           self._bump_traces, donate)

    def _bump_traces(self):
        self._trace_count += 1

    def _run_dispatch(self, key, build, donate, args, rows=0, **fields):
        """Every compiled dispatch goes through here: resolves the
        jitted executable (trace-spied as before) and, when the
        telemetry ring is on, logs ONE step-timeline event — kind from
        generation.dispatch_kind(key), dispatch-side elapsed, trace-spy
        delta (a compile mid-flight shows as traces_delta >= 1), and
        gauge snapshots for the counter tracks. Returns (out, event);
        the caller attaches harvest results via Telemetry.finish_step.
        Telemetry off = exactly the old call (no clock reads)."""
        fn = self._counted_jit(key, build, donate=donate)
        tele = self.telemetry
        if not tele.enabled:
            return fn(*args), None
        t0 = self.clock()
        tr0 = self._traces_total()
        out = fn(*args)
        t1 = self.clock()
        ev = tele.step_event(
            dispatch_kind(key), t0, t1 - t0, rows=rows,
            traces_delta=self._traces_total() - tr0,
            queue_depth=self.queue_depth,
            kv_blocks_used=(self.pool.used if self.paged else None),
            **fields)
        return out, ev

    def _core(self):
        core = getattr(self, "_core_cache", None)
        if core is None:
            core = self.dec._build_step_core(
                self.do_sample, self.top_k, self.top_p, self.temperature)
            self._core_cache = core
        return core

    # ------------------------------------------------------ paged plumbing
    def _cache_arg(self):
        """The compiled-step cache operand: dense -> the ring buffer
        as-is; paged -> the pool dict plus this dispatch's block tables
        (tiny [B, Smax/Bt] int32, re-uploaded from host state per call
        — block ids are DATA, so table churn never retraces)."""
        if not self.paged:
            return self._caches
        return dict(self._caches, tbl=jnp.asarray(self._tables))

    def _keep_caches(self, out):
        if not self.paged:
            self._caches = out
        else:
            self._caches = {k: v for k, v in out.items() if k != "tbl"}

    def _blocks_needed(self, plen, max_new):
        """Worst-case pool blocks for one request: every position in
        [0, plen + max_new) mapped. The submit-time Smax bound keeps
        this <= Smax/Bt."""
        return -(-(int(plen) + int(max_new)) // self.prefill_cap)

    def _alloc_kv_blocks(self, n):
        got = self.pool.alloc(n)
        if got is None:
            store = getattr(self.prefix_cache, "store", None)
            if store is not None and hasattr(store, "reclaim"):
                # prefix blocks are CACHE: evict cold ones under memory
                # pressure before touching the reservation guarantees
                store.reclaim(n - self.pool.free_count)
            got = self.pool.alloc(n)
        if got is None:
            raise RuntimeError(
                f"kv block pool over-committed: need {n} blocks, "
                f"{self.pool.free_count} free after reclaim — the "
                "admission-time reservation accounting should make "
                "this unreachable")
        return got

    def _map_blocks(self, slot, hi):
        """Lazily map pool blocks so the slot's table covers positions
        [0, hi) — called as lens grows (admission covers the prompt;
        each decode/verify dispatch covers its write window)."""
        row = self._tables[slot]
        nb = self.pool.num_blocks
        need = [j for j in range(-(-int(hi) // self.prefill_cap))
                if row[j] == nb]
        if need:
            row[need] = self._alloc_kv_blocks(len(need))

    def _budget_pos(self, slot):
        """One-past the slot's LAST possible write position: lens peaks
        at plen + max_new - 1 (the submit-time bound), and every
        masked/dropped write targets a position below it too — so the
        write-window mapping must never touch a block past this, or a
        tightly sized pool would be asked for blocks beyond the
        admission-time worst-case reservation."""
        return (int(self._lens[slot]) - int(self._nt[slot])
                + int(self._max_nt[slot]))

    def _ensure_writable(self, slot, lo, hi):
        """COW guard + lazy mapping for the write window [lo, hi): an
        unmapped block allocates; a SHARED block (refcount > 1 — prefix
        blocks another slot/the store also references, or a fork twin)
        is copied-on-write first, so a write can never leak into
        someone else's view. In the steady serving flow writes land
        strictly past every shared block (adoption/publication are
        block-aligned below plen), so the copy only ever fires for
        forked slots."""
        hi = min(int(hi), self.smax)
        if hi <= lo:
            return
        row = self._tables[slot]
        nb = self.pool.num_blocks
        bt = self.prefill_cap
        for j in range(int(lo) // bt, (hi - 1) // bt + 1):
            blk = int(row[j])
            if blk == nb:
                row[j] = self._alloc_kv_blocks(1)[0]
            elif int(self.pool.refcounts[blk]) > 1:
                new = self._alloc_kv_blocks(1)[0]
                self._caches = self.pool.copy_block(self._caches, blk,
                                                    new)
                row[j] = new
                self.pool.deref([blk])
                self._cow_copies += 1

    def _free_slot_blocks(self, slot):
        row = self._tables[slot]
        nb = self.pool.num_blocks
        mapped = [int(x) for x in row[row < nb]]
        if mapped:
            self.pool.deref(mapped)
        row[:] = nb

    def fork_slot(self, rid, max_new_tokens=None):
        """Copy-on-write FORK of a running request (paged mode): clone
        its decode state into a free slot, sharing every KV block
        through the block table (pool refcounts; ZERO data movement).
        The twins then decode independently — the first write into a
        still-shared block triggers the copy-on-write of just that
        block. This is the parallel-sampling / N-best primitive the
        paged layout gives for free; returns the child's request id.

        The child inherits the parent's generated-so-far tokens and
        budget (``max_new_tokens`` overrides the remaining total)."""
        if not self.paged:
            raise ValueError("fork_slot needs the paged KV cache "
                             "(PADDLE_SERVING_PAGED=0 disables it)")
        src = None
        for r in self._slot_req:
            if r is not None and r.rid == rid:
                src = r
        if src is None or src.state != "running":
            raise ValueError(f"request {rid} is not running in a slot")
        free = self._free_slots()
        if not free:
            # shed like submit() sheds: the rejection must show up in
            # the overload metric, not vanish
            self._rejected += 1
            if self.telemetry.enabled:
                self.telemetry.req_rejected(self.clock())
            raise AdmissionFull("no free slot to fork into")
        s0, s1 = src.slot, free[0]
        mnt = int(max_new_tokens if max_new_tokens is not None
                  else src.max_new_tokens)
        if src.prompt.size + mnt > self.smax:
            raise ValueError("fork budget exceeds the ring capacity")
        need = self._blocks_needed(src.prompt.size, mnt)
        if self._kv_reserved + need > self.pool.num_blocks:
            self._rejected += 1
            if self.telemetry.enabled:
                self.telemetry.req_rejected(self.clock())
            raise AdmissionFull(
                f"kv pool exhausted: fork needs {need} blocks, "
                f"{self.pool.num_blocks - self._kv_reserved} unreserved")
        child = ServedRequest(next(self._rid), src.prompt, mnt,
                              src.eos_token_id, src.min_length,
                              src.repetition_penalty, self.clock(),
                              seed=self._fresh_seed(),
                              trace_id=src.trace_id, attempt=src.attempt,
                              priority=src.priority)
        child.state = "running"
        child.slot = s1
        child.t_admit = child.t_submit    # a clone never queues
        child.tokens = list(src.tokens)
        child.t_first = src.t_first
        self._slot_req[s1] = child
        self._req_index[child.rid] = child
        self._kv_reserved += need
        self._kv_committed += need
        # a fork is a CLONE, not an admission: it performs no prefix
        # lookup, so counting it as admitted would break the
        # hits + misses == admitted reconciliation conftest pins
        self._forked += 1
        if self.telemetry.enabled:
            self.telemetry.req_queued(child.rid, child.t_submit,
                                      trace_id=child.trace_id,
                                      attempt=child.attempt)
            self.telemetry.req_admitted(child.rid, s1, child.t_submit)
            self.telemetry.req_event(child.rid, "forked", child.t_submit)
        # share the parent's blocks: table row copy + one ref each
        row = self._tables[s0]
        mapped = [int(x) for x in row[row < self.pool.num_blocks]]
        self.pool.ref(mapped)
        self._tables[s1] = row
        for vec in (self._lens, self._nt, self._eos, self._min_len,
                    self._rep_pen, self._tok):
            vec[s1] = vec[s0]
        self._max_nt[s1] = mnt
        # the child samples from its OWN seed stream: under the
        # scheduling-invariant per-request sampling discipline, twins
        # sharing the parent's seed would decode IDENTICAL suffixes —
        # the whole point of a fork is divergent continuations
        self._rseed[s1] = child.seed
        # a mid-prefill parent forks cleanly: the child inherits the
        # prefill cursor and streams the remaining prompt through the
        # budget packer like any prefilling slot (its writes trigger
        # COW on the still-shared prompt blocks)
        self._pf_left[s1] = self._pf_left[s0]
        self._active[s1] = self._active[s0] and self._nt[s1] < mnt
        if self._drafters is not None:
            self._drafters[s1].reset(src.prompt)
            self._drafters[s1].update(child.tokens)
        if self._rep_on:
            p = self._presence_init()
            self._presence = p.at[s1].set(p[s0])
        if not self._active[s1] and not self._pf_left[s1]:
            self._finish(child, self.clock())
        return child.rid

    # ------------------------------------------------------ live migration
    # The cluster-drain primitive: a live request's COMPLETE decode state
    # — committed KV blocks (host bytes via BlockPool.read_block), lens /
    # nt / next input token / prefill cursor, per-request sampler seed,
    # and the request contract (prompt, budget, eos, penalties, trace
    # context) — detaches from this engine and resumes MID-STREAM on
    # another one. Drafter n-gram maps and the repetition-penalty
    # presence row are NOT shipped: both are deterministic functions of
    # prompt + generated tokens and are rebuilt at import, byte-
    # equivalent to the live state (the drafter inserts incrementally in
    # exactly the order update() replays; presence is the one-hot union).
    # Greedy continuations are token-identical by construction; plain
    # sampled mode is too (the seed moves and every draw is
    # fold_in(seed, nt)); spec-decode sampled mode redraws its host
    # rejection RNG — the documented caveat.
    MIGRATION_FMT = "paddle-slot-v1"

    def export_slot(self, rid, skip_blocks=0):
        """Detach request ``rid`` (queued, running, or a held
        ``prefilled`` slot on a prefill-role engine) into a
        JSON/pickle-able migration state dict and free everything it
        held here (slot, block references, reservations). The request's
        record leaves this engine as state ``migrated`` — it is neither
        finished nor expired, so no latency/SLO verdict is recorded.
        Paged engines only (the payload IS pool blocks).

        ``skip_blocks`` supports the STREAMED handoff: the first N
        blocks are assumed already staged on the importing engine
        (export_kv_prefix -> stage_kv_blocks while prefill was still
        running), so they are neither re-read nor re-shipped — the
        state dict records ``kv_skip`` and import_slot splices the
        staged blocks back in. A held ``prefilled`` slot exports with
        ``active=True``: its first token is sampled but decode has not
        started, and the importer must resume decoding, not
        instant-finish at the boundary."""
        if not self.paged:
            raise ValueError("export_slot needs the paged KV cache "
                             "(the migration payload is pool blocks; "
                             "PADDLE_SERVING_PAGED=0 disables it)")
        req = self._req_index.get(rid)
        if req is None or req.state not in ("queued", "running",
                                            "prefilled"):
            raise ValueError(f"request {rid} is not live on this engine")
        now = self.clock()
        skip_blocks = int(skip_blocks)
        state = {
            "fmt": self.MIGRATION_FMT,
            "prompt": np.asarray(req.prompt, np.int32),
            "tokens": [int(t) for t in req.tokens],
            "max_new_tokens": req.max_new_tokens,
            "eos_token_id": req.eos_token_id,
            "min_length": req.min_length,
            "repetition_penalty": req.repetition_penalty,
            "deadline_s": req.deadline_s,
            "seed": req.seed,
            "trace_id": req.trace_id,
            "attempt": req.attempt,
            "priority": req.priority,
            "prefill_cap": self.prefill_cap,
            "lens": 0, "nt": 0, "tok": 0, "active": False,
            "pf_left": int(req.prompt.size),
            "kv_skip": 0,
            "kv": [],
        }
        need = self._blocks_needed(req.prompt.size, req.max_new_tokens)
        if req.state == "queued":
            self._queue_remove(req)
            self._kv_committed -= need
        else:
            s = req.slot
            state.update(
                lens=int(self._lens[s]), nt=int(self._nt[s]),
                tok=int(self._tok[s]),
                # a held prefilled slot was deactivated only to park it
                # — the importer must treat it as mid-decode (there are
                # tokens left to generate by construction: a request
                # finishing on its first token never parks)
                active=(bool(self._active[s])
                        or req.state == "prefilled"),
                pf_left=int(self._pf_left[s]))
            if req.state == "prefilled" and req.tokens:
                # dispatches batched AFTER the hold overwrite the
                # per-slot sampled-token vector for inactive rows —
                # the request's own emit history is the durable copy
                # of the token decode resumes from
                state["tok"] = int(req.tokens[-1])
            # KV entries written so far live in [0, lens) — the next
            # token's K/V lands at `lens` on the IMPORTING engine
            # (write-then-attend), so the partial tail block travels
            # as-is and decode resumes seamlessly
            row = self._tables[s]
            total = -(-state["lens"] // self.prefill_cap)
            if not 0 <= skip_blocks <= total:
                raise ValueError(
                    f"skip_blocks={skip_blocks} outside the request's "
                    f"committed block count [0, {total}]")
            state["kv_skip"] = skip_blocks
            for j in range(skip_blocks, total):
                state["kv"].append(
                    self.pool.read_block(self._caches, int(row[j])))
            self._kv_committed -= need
            self._kv_reserved -= need
            self._slot_req[s] = None
            self._active[s] = False
            self._pf_left[s] = 0
            self._free_slot_blocks(s)
        req.state = "migrated"
        self._req_index.pop(rid, None)
        self._harvest.pop(rid, None)
        self._migrated_out += 1
        if state["kv"]:
            self._kv_blocks_shipped += len(state["kv"])
            self.telemetry.observe_handoff(_kv_payload_bytes(state["kv"]))
        if self.telemetry.enabled:
            self.telemetry.req_event(rid, "migrate_out", now)
        self.telemetry.req_done(rid, "migrated", now)
        return state

    def import_slot(self, state, staged=None):
        """Resume an exported request on THIS engine: allocate fresh
        pool blocks, upload the KV bytes, restore the decode vectors,
        and rebuild the derived per-slot state (drafter, presence) from
        the token history. Returns the request's NEW rid here. Sheds
        honestly with ``AdmissionFull`` when no slot or no pool headroom
        can take it — the caller (router drain) falls back to classic
        failover. A never-prefilled export (queued, zero KV) re-enters
        the queue instead of claiming a slot.

        ``staged`` names a stage_kv_blocks tag whose blocks arrived
        AHEAD of this import (streamed handoff): they must cover
        exactly the export's ``kv_skip`` leading blocks and are spliced
        in as the slot's leading table entries — already resident, so
        only the remainder uploads here and the import cost overlaps
        the prefill tail instead of serializing after it. A shed import
        leaves the staged blocks in place (the caller retries or
        abort_stage()s them)."""
        if not self.paged:
            raise ValueError("import_slot needs the paged KV cache")
        if not isinstance(state, dict) or \
                state.get("fmt") != self.MIGRATION_FMT:
            raise ValueError(
                f"not a migration state dict (fmt="
                f"{None if not isinstance(state, dict) else state.get('fmt')!r}"
                f", expected {self.MIGRATION_FMT!r})")
        if int(state["prefill_cap"]) != self.prefill_cap:
            raise ValueError(
                f"migration state has prefill_cap={state['prefill_cap']}"
                f" but this engine uses {self.prefill_cap} — the KV "
                "blocks are prefill_cap-sized and cannot be re-chunked")
        prompt = np.asarray(state["prompt"], np.int32).reshape(-1)
        max_new = int(state["max_new_tokens"])
        if prompt.size + max_new > self.smax:
            raise ValueError(
                f"migrated request needs {prompt.size} + {max_new} "
                f"positions but this engine's Smax is {self.smax}")
        lens = int(state["lens"])
        if not 0 <= lens <= prompt.size + max_new:
            # without this bound a corrupt payload with a huge lens
            # (and a matching kv list) would pass the count check below
            # and allocate blocks past the admission-time reservation —
            # breaking the pool's over-commit invariant mid-serving
            # instead of shedding the one bad import here
            raise ValueError(
                f"migration state has lens={lens} outside its own "
                f"request budget [0, {prompt.size} + {max_new}] — "
                "corrupt or mismatched payload")
        blocks = state["kv"]
        kv_skip = int(state.get("kv_skip", 0))
        staged_ids = []
        if staged is not None:
            got = self._staged.get(staged)
            if got is None:
                raise ValueError(
                    f"no staged kv blocks under tag {staged!r}")
            staged_ids = got
        if len(staged_ids) != kv_skip:
            raise ValueError(
                f"export skips {kv_skip} leading kv blocks but "
                f"{len(staged_ids)} are staged under "
                f"{staged!r} — the streamed prefix must cover the skip "
                "exactly")
        total_blocks = -(-lens // self.prefill_cap)
        if kv_skip + len(blocks) != total_blocks:
            raise ValueError(
                f"migration state ships {len(blocks)} kv blocks "
                f"(+{kv_skip} staged) but lens={lens} needs "
                f"{total_blocks}")
        kv_shape = self._caches["kv"].shape      # [L, 2, NB, H, Bt, D]
        want = (kv_shape[0], 2, 1, kv_shape[3], kv_shape[4], kv_shape[5])
        for blk in blocks:
            if tuple(blk["kv"].shape) != want:
                raise ValueError(
                    f"migrated kv block shape {tuple(blk['kv'].shape)} "
                    f"does not match this pool's {want} — the engines' "
                    "model/layout configs must agree")
            if ("sc" in self._caches) != ("sc" in blk):
                raise ValueError(
                    "migrated block cache flavor (int8 scales) does not "
                    "match this engine's")
        now = self.clock()
        need = self._blocks_needed(prompt.size, max_new)
        tokens = [int(t) for t in state["tokens"]]
        req = ServedRequest(next(self._rid), prompt, max_new,
                            state["eos_token_id"],
                            int(state["min_length"]),
                            float(state["repetition_penalty"]), now,
                            deadline_s=state["deadline_s"],
                            seed=int(state["seed"]),
                            trace_id=state["trace_id"],
                            attempt=int(state["attempt"]),
                            priority=state.get("priority", QOS_DEFAULT))
        if not blocks and not staged_ids and not tokens \
                and int(state["nt"]) == 0:
            # never prefilled: the import is a plain (re-)queue — it
            # will be ADMITTED normally later (prefix lookup included)
            if self.max_pending and self._queue_len() >= self.max_pending:
                self._rejected += 1
                if self.telemetry.enabled:
                    self.telemetry.req_rejected(
                        now, trace_id=req.trace_id, attempt=req.attempt)
                raise AdmissionFull(
                    f"pending queue full ({self._queue_len()}/"
                    f"{self.max_pending}) — migrated request shed")
            if self._kv_gate and \
                    self._kv_committed + need > self.pool.num_blocks:
                self._rejected += 1
                if self.telemetry.enabled:
                    self.telemetry.req_rejected(
                        now, trace_id=req.trace_id, attempt=req.attempt)
                raise AdmissionFull("kv pool exhausted — migrated "
                                    "request shed at import")
            self._kv_committed += need
            if staged is not None:
                self._staged.pop(staged, None)   # empty tag, consumed
            self._queues[req.priority].append(req)
            self._req_index[req.rid] = req
            self._migrated_in += 1
            self.telemetry.req_queued(req.rid, now,
                                      trace_id=req.trace_id,
                                      attempt=req.attempt)
            if self.telemetry.enabled:
                self.telemetry.req_event(req.rid, "migrate_in", now)
            return req.rid
        free = self._free_slots()
        if not free:
            self._rejected += 1
            if self.telemetry.enabled:
                self.telemetry.req_rejected(now, trace_id=req.trace_id,
                                            attempt=req.attempt)
            raise AdmissionFull("no free slot to import the migrated "
                                "session into")
        if self._kv_reserved - len(staged_ids) + need \
                > self.pool.num_blocks:
            # staged blocks already hold their own reservation (made at
            # stage_kv_blocks) — it transfers into this request's
            # worst-case reservation on success, so only the DELTA is
            # checked here
            self._rejected += 1
            if self.telemetry.enabled:
                self.telemetry.req_rejected(now, trace_id=req.trace_id,
                                            attempt=req.attempt)
            raise AdmissionFull(
                f"kv pool exhausted: migrated session needs {need} "
                f"blocks, {self.pool.num_blocks - self._kv_reserved} "
                "unreserved")
        s = free[0]
        req.state = "running"
        req.slot = s
        req.t_admit = now                  # queue time on THIS engine: 0
        # TTFT belongs to the attempt that produced the first token —
        # a stream that already emitted keeps t_first unset here (the
        # TTFT histogram legitimately sees fewer entries than finished)
        req.tokens = tokens
        if staged_ids:
            # consume the staged prefix: its standalone reservation
            # folds into the request's, and the blocks become the
            # slot's leading table entries — no re-upload
            del self._staged[staged]
            self._kv_reserved -= len(staged_ids)
        self._kv_committed += need
        self._kv_reserved += need
        new_ids = self._alloc_kv_blocks(len(blocks)) if blocks else []
        for blk, dst in zip(blocks, new_ids):
            self._caches = self.pool.write_block(self._caches, blk, dst)
        self._kv_blocks_adopted += len(blocks)
        ids = list(staged_ids) + list(new_ids)
        row = self._tables[s]
        row[:] = self.pool.num_blocks
        row[:len(ids)] = ids
        self._lens[s] = lens
        self._nt[s] = int(state["nt"])
        self._tok[s] = int(state["tok"])
        self._max_nt[s] = max_new
        self._eos[s] = (-1 if req.eos_token_id is None
                        else int(req.eos_token_id))
        self._min_len[s] = req.min_length
        self._rep_pen[s] = req.repetition_penalty
        self._rseed[s] = req.seed
        self._active[s] = bool(state["active"])
        self._pf_left[s] = int(state["pf_left"])
        if self._drafters is not None:
            # the n-gram maps are a pure function of the token history:
            # reset + update replays exactly the live insert order
            self._drafters[s].reset(prompt)
            self._drafters[s].update(tokens)
        if self._rep_on:
            vocab = self._presence_init().shape[1]
            rowv = np.zeros(vocab, bool)
            rowv[prompt] = True
            if tokens:
                rowv[np.asarray(tokens, np.int64)] = True
            self._presence = self._presence_init().at[s].set(
                jnp.asarray(rowv))
        self._slot_req[s] = req
        self._req_index[req.rid] = req
        self._migrated_in += 1
        self.telemetry.req_queued(req.rid, now, trace_id=req.trace_id,
                                  attempt=req.attempt)
        self.telemetry.req_admitted(req.rid, s, now)
        if self.telemetry.enabled:
            self.telemetry.req_event(req.rid, "migrate_in", now)
        if not self._active[s] and not self._pf_left[s] and tokens:
            # exported at the exact finish boundary: complete instantly
            self._finish(req, now)
        elif (self.role == "prefill" and self._active[s]
                and not self._pf_left[s] and self._nt[s] >= 1):
            # a prompt-complete session landing on a prefill worker
            # (handoff bounce-back after a decode-pool shed race)
            # re-holds immediately — a prefill engine never decodes
            req.state = "prefilled"
            self._active[s] = False
        return req.rid

    # ------------------------------------------------- streamed KV handoff
    def export_kv_prefix(self, rid, start_block=0, min_blocks=1):
        """Read the COMMITTED full KV blocks of a live request without
        detaching it — the streamed-handoff source primitive. Returns
        ``(blocks, n_full)`` where blocks covers pool block indices
        [start_block, n_full) of the slot's table (n_full = lens //
        prefill_cap: only FULL blocks ship early; the partial tail
        block travels with the final export_slot). The request keeps
        running — the router overlaps stage_kv_blocks on the decode
        target with the remaining prefill, so the final transfer is
        just the tail + bookkeeping and TTFT ~ prefill time. Blocks in
        [start_block, n_full) ship exactly once per cursor advance;
        the caller owns the cursor."""
        if not self.paged:
            raise ValueError("export_kv_prefix needs the paged KV cache")
        req = self._req_index.get(rid)
        if req is None or req.state not in ("running", "prefilled") \
                or req.slot is None:
            raise ValueError(f"request {rid} is not resident in a slot")
        s = req.slot
        n_full = int(self._lens[s]) // self.prefill_cap
        start_block = int(start_block)
        if not 0 <= start_block <= n_full:
            raise ValueError(
                f"start_block={start_block} outside [0, {n_full}]")
        if n_full - start_block < max(1, int(min_blocks)):
            # below the caller's chunk threshold: answer without
            # reading so the shipped counter stays exact (every
            # counted block left the pool exactly once per cursor)
            return [], n_full
        row = self._tables[s]
        blocks = [self.pool.read_block(self._caches, int(row[j]))
                  for j in range(start_block, n_full)]
        if blocks:
            self._kv_blocks_shipped += len(blocks)
            self.telemetry.observe_handoff(_kv_payload_bytes(blocks))
            if self.telemetry.enabled:
                self.telemetry.req_event(rid, "kv_ship", self.clock())
        return blocks, n_full

    def stage_kv_blocks(self, tag, blocks):
        """Receive streamed KV blocks AHEAD of their session's import:
        allocate pool blocks (under a staging reservation — the
        admission guarantee that every lazy mapping is satisfiable
        must hold with staged blocks resident), upload the payloads,
        and file the ids under ``tag`` for import_slot(staged=tag) to
        splice in. Repeat calls append (one tag accumulates a prefix
        block-by-block as prefill commits them). Sheds with
        ``AdmissionFull`` when the pool cannot take the blocks — the
        staged prefix so far stays put. Returns the total staged count
        under the tag."""
        if not self.paged:
            raise ValueError("stage_kv_blocks needs the paged KV cache")
        blocks = list(blocks)
        kv_shape = self._caches["kv"].shape      # [L, 2, NB, H, Bt, D]
        want = (kv_shape[0], 2, 1, kv_shape[3], kv_shape[4], kv_shape[5])
        for blk in blocks:
            if tuple(blk["kv"].shape) != want:
                raise ValueError(
                    f"staged kv block shape {tuple(blk['kv'].shape)} "
                    f"does not match this pool's {want}")
            if ("sc" in self._caches) != ("sc" in blk):
                raise ValueError(
                    "staged block cache flavor (int8 scales) does not "
                    "match this engine's")
        if blocks and self._kv_reserved + len(blocks) \
                > self.pool.num_blocks:
            raise AdmissionFull(
                f"kv pool exhausted: staging {len(blocks)} blocks, "
                f"{self.pool.num_blocks - self._kv_reserved} unreserved")
        if blocks:
            self._kv_reserved += len(blocks)
            ids = self._alloc_kv_blocks(len(blocks))
            for blk, dst in zip(blocks, ids):
                self._caches = self.pool.write_block(self._caches, blk,
                                                     dst)
            self._kv_blocks_adopted += len(blocks)
            self._staged.setdefault(tag, []).extend(ids)
        elif tag not in self._staged:
            self._staged[tag] = []
        return len(self._staged[tag])

    def abort_stage(self, tag):
        """Drop a staging tag: free its pool blocks + reservation (the
        handoff fell through — target raced a shed, source died, the
        session finished on the prefill worker). Idempotent; returns
        the number of blocks released."""
        ids = self._staged.pop(tag, None)
        if not ids:
            return 0
        self.pool.deref(ids)
        self._kv_reserved -= len(ids)
        return len(ids)

    # ----------------------------------------------------- QoS preemption
    # Preemption-to-host reuses the migration serialization (the state
    # dict IS a MIGRATION_FMT payload) but keeps the request FIRST-CLASS
    # on this engine: same rid, same _req_index entry (state
    # "preempted"), same tokens list and streaming-harvest cursor — so a
    # tracked reader sees one continuous exactly-once stream across the
    # park/resume legs with zero router involvement. _kv_committed stays
    # held while parked (the request still intends to run here; releasing
    # it would let submit() overcommit the pool against a request that
    # WILL come back); only the running-worst-case reservation
    # (_kv_reserved) and the physical blocks are released.
    def preempt_to_host(self, rid):
        """Preempt a RUNNING request into the host-RAM parking lot:
        serialize its full decode state (KV bytes included), free the
        slot + physical blocks, and keep the request indexed as
        ``preempted``. resume_from_host() restores it token-identically
        (greedy AND plain-sampled — the seed rides the state and every
        draw is fold_in(seed, nt)). Paged engines only."""
        if not self.paged:
            raise ValueError("preempt_to_host needs the paged KV cache "
                             "(the parked payload is pool blocks; "
                             "PADDLE_SERVING_PAGED=0 disables it)")
        req = self._req_index.get(rid)
        if req is None or req.state != "running":
            raise ValueError(f"request {rid} is not running in a slot")
        now = self.clock()
        s = req.slot
        state = {
            "fmt": self.MIGRATION_FMT,
            "prompt": np.asarray(req.prompt, np.int32),
            "tokens": [int(t) for t in req.tokens],
            "max_new_tokens": req.max_new_tokens,
            "eos_token_id": req.eos_token_id,
            "min_length": req.min_length,
            "repetition_penalty": req.repetition_penalty,
            "deadline_s": req.deadline_s,
            "seed": req.seed,
            "trace_id": req.trace_id,
            "attempt": req.attempt,
            "priority": req.priority,
            "prefill_cap": self.prefill_cap,
            "lens": int(self._lens[s]), "nt": int(self._nt[s]),
            "tok": int(self._tok[s]), "active": bool(self._active[s]),
            "pf_left": int(self._pf_left[s]),
            "kv": [],
        }
        row = self._tables[s]
        for j in range(-(-state["lens"] // self.prefill_cap)):
            state["kv"].append(
                self.pool.read_block(self._caches, int(row[j])))
        need = self._blocks_needed(req.prompt.size, req.max_new_tokens)
        self._kv_reserved -= need
        self._slot_req[s] = None
        self._active[s] = False
        self._pf_left[s] = 0
        self._free_slot_blocks(s)
        req.slot = None
        req.state = "preempted"
        # the injected-fault window: slot freed, parking insert pending.
        # A raise here loses the parked copy — the replica dies and the
        # router's CLASSIC failover (delivered-prefix skip) replays the
        # stream exactly-once elsewhere; pinned by test.
        from ..testing import fault
        fault.inject("preempt")
        self._parked[rid] = state
        self._preempted += 1
        if self.telemetry.enabled:
            self.telemetry.req_event(rid, "preempt", now)
        return rid

    def resume_from_host(self, rid):
        """Re-import a parked request into a free slot (fresh physical
        blocks, KV bytes re-uploaded, drafter/presence rebuilt from the
        token history). Sheds with ``AdmissionFull`` when no slot or no
        reservation headroom can take it — the parked copy stays put and
        a later pass retries. t_submit/t_admit/deadline are UNTOUCHED:
        the deadline clock keeps running while parked (park time is
        queue-attributed delay, never a budget refill)."""
        state = self._parked.get(rid)
        req = self._req_index.get(rid)
        if state is None or req is None or req.state != "preempted":
            raise ValueError(f"request {rid} is not parked here")
        free = self._free_slots()
        if not free:
            raise AdmissionFull("no free slot to resume the parked "
                                "request into")
        need = self._blocks_needed(req.prompt.size, req.max_new_tokens)
        if self._kv_reserved + need > self.pool.num_blocks:
            raise AdmissionFull(
                f"kv pool exhausted: resume needs {need} blocks, "
                f"{self.pool.num_blocks - self._kv_reserved} unreserved")
        now = self.clock()
        s = free[0]
        del self._parked[rid]
        blocks = state["kv"]
        self._kv_reserved += need          # committed never left
        ids = self._alloc_kv_blocks(len(blocks)) if blocks else []
        for blk, dst in zip(blocks, ids):
            self._caches = self.pool.write_block(self._caches, blk, dst)
        row = self._tables[s]
        row[:] = self.pool.num_blocks
        row[:len(ids)] = ids
        self._lens[s] = int(state["lens"])
        self._nt[s] = int(state["nt"])
        self._tok[s] = int(state["tok"])
        self._max_nt[s] = req.max_new_tokens
        self._eos[s] = (-1 if req.eos_token_id is None
                        else int(req.eos_token_id))
        self._min_len[s] = req.min_length
        self._rep_pen[s] = req.repetition_penalty
        self._rseed[s] = req.seed
        self._active[s] = bool(state["active"])
        self._pf_left[s] = int(state["pf_left"])
        if self._drafters is not None:
            self._drafters[s].reset(req.prompt)
            self._drafters[s].update(req.tokens)
        if self._rep_on:
            vocab = self._presence_init().shape[1]
            rowv = np.zeros(vocab, bool)
            rowv[req.prompt] = True
            if req.tokens:
                rowv[np.asarray(req.tokens, np.int64)] = True
            self._presence = self._presence_init().at[s].set(
                jnp.asarray(rowv))
        req.slot = s
        req.state = "running"
        self._slot_req[s] = req
        self._resumed += 1
        if self.telemetry.enabled:
            self.telemetry.req_event(rid, "resume", now)
        if not self._active[s] and not self._pf_left[s] and req.tokens:
            self._finish(req, now)
        return rid

    def _qos_schedule(self):
        """One scheduling pass per step (paged engines only): resume
        parked requests best-class-first while there is headroom, then —
        when a strictly better-class queue head is blocked on slots or
        on the kv reservation — preempt the single worst (lowest-class,
        youngest) running victim to the parking lot. At most one
        preemption per step keeps the pass O(slots) and lets the freed
        capacity be re-measured before the next eviction."""
        if not self.paged:
            return
        # resume pass: parked requests compete in class order; stop at
        # the first one that doesn't fit (FIFO-within-class fairness),
        # and never jump ahead of a strictly better queued head
        for rid in sorted(self._parked,
                          key=lambda r: (QOS_RANK[
                              self._parked[r]["priority"]], r)):
            head = self._queue_head()
            if head is not None and QOS_RANK[head.priority] < \
                    QOS_RANK[self._parked[rid]["priority"]]:
                break
            try:
                self.resume_from_host(rid)
            except AdmissionFull:
                break
        head = self._queue_head()
        if head is None:
            return
        need = self._blocks_needed(head.prompt.size,
                                   head.max_new_tokens)
        blocked = (not self._free_slots()
                   or self._kv_reserved + need > self.pool.num_blocks)
        if not blocked:
            return
        victims = [r for r in self._slot_req
                   if r is not None and r.state == "running"
                   and QOS_RANK[r.priority] > QOS_RANK[head.priority]]
        if not victims:
            return
        victim = max(victims,
                     key=lambda r: (QOS_RANK[r.priority], r.rid))
        self.preempt_to_host(victim.rid)

    def _prefill_allocations(self, pf_rows, budget, col_cap=None):
        """Weighted-fair split of this dispatch's prefill budget across
        QoS classes — pure host arithmetic over which rows advance their
        prefill cursors, so the dispatch shapes (and therefore the
        executables) never change. Two passes: (1) proportional — each
        class with waiting prefill work gets floor(budget * share /
        total_shares) tokens, spent FCFS-by-rid within the class; (2)
        work-conserving spill — leftover budget (idle classes, floors,
        capped rows) goes to remaining demand in (class-rank, rid)
        order. With a SINGLE class present pass 1 is skipped and the
        result is exactly the old FCFS packing — token-identical to the
        pre-QoS scheduler. Returns ([(slot, n), ...] ordered by
        (class-rank, rid), remaining_budget)."""
        order = sorted(pf_rows,
                       key=lambda s: (QOS_RANK[self._slot_req[s].priority],
                                      self._slot_req[s].rid))
        cap = budget if col_cap is None else col_cap
        want = {s: min(int(self._pf_left[s]), cap) for s in order}
        alloc = {s: 0 for s in order}
        classes = {self._slot_req[s].priority for s in order}
        if len(classes) > 1:
            total = sum(self.qos_shares[c] for c in classes)
            for c in classes:
                fair = budget * self.qos_shares[c] // total
                for s in order:
                    if self._slot_req[s].priority != c:
                        continue
                    n = min(want[s] - alloc[s], fair)
                    alloc[s] += n
                    fair -= n
        spent = sum(alloc.values())
        left = budget - spent
        for s in order:
            if left <= 0:
                break
            n = min(want[s] - alloc[s], left)
            alloc[s] += n
            left -= n
        return [(s, alloc[s]) for s in order if alloc[s] > 0], left

    def _build_decode_chunk(self):
        """The ONE compiled decode step: decode_chunk tokens per dispatch
        over all B slots, each at its own depth (the scan length comes
        from the `keys` argument the caller builds, one key per token).
        Finish bookkeeping (per-slot eos / max_new_tokens) runs on-device
        inside the scan; the host only sees the per-step (token,
        emitted-mask) ys at the chunk boundary."""
        core = self._core()
        hidden, head_logits = core.hidden, core.head_logits
        rep_on = self._rep_on
        do_sample = self.do_sample
        top_k, top_p, temp = self.top_k, self.top_p, self.temperature
        chunk = self.decode_chunk

        def decode_chunk(stk, e_arrays, h_arrays, caches, tok, lens,
                         active, nt, max_nt, eos_ids, min_len, rep_pen,
                         presence, seeds):
            def body(carry, _):
                tok, caches, lens, active, nt, presence = carry
                x, caches = hidden(stk, e_arrays, caches, tok, lens)
                logits = head_logits(h_arrays, x)
                logits = logits.reshape(logits.shape[0], -1)
                logits = _penalize_slots(
                    logits, presence if rep_on else None, rep_pen, nt,
                    min_len, eos_ids)
                # per-row keys fold (request seed, nt): sampling is
                # invariant to chunk boundaries and scheduling
                nxt = _sample_rows(logits, do_sample, top_k, top_p,
                                   temp, seeds, nt)
                emitted = active
                hit_eos = (eos_ids >= 0) & (nxt == eos_ids)
                step = active.astype(jnp.int32)
                nt = nt + step
                lens = lens + step
                active = active & ~hit_eos & (nt < max_nt)
                tok = jnp.where(emitted, nxt, tok)
                if rep_on:
                    presence = presence.at[
                        jnp.arange(nxt.shape[0]), nxt].max(emitted)
                carry = (tok, caches, lens, active, nt, presence)
                return carry, (nxt, emitted)
            carry, ys = jax.lax.scan(
                body, (tok, caches, lens, active, nt, presence), None,
                length=chunk)
            tok, caches, lens, active, nt, presence = carry
            return caches, tok, lens, active, nt, presence, ys
        return decode_chunk

    def _build_prefill_chunk(self, chunk):
        """In-slot prefill: `chunk` teacher-forced tokens, per-row start
        positions and per-row valid counts. Rows outside their valid
        range (and slots not being admitted, n_valid == 0) are write-
        masked — their cache rows cannot be touched. Each admitted row's
        LAST valid hidden state is captured into last_x."""
        hidden = self._core().hidden

        def prefill(stk, e_arrays, caches, toks, t0, n_valid, last_x):
            def body(carry, xs):
                caches, last_x = carry
                tok_i, i = xs
                mask = i < n_valid
                x, caches = hidden(stk, e_arrays, caches, tok_i, t0 + i,
                                   mask)
                last_x = jnp.where(mask[:, None, None], x, last_x)
                return (caches, last_x), None
            (caches, last_x), _ = jax.lax.scan(
                body, (caches, last_x),
                (toks, jnp.arange(chunk, dtype=jnp.int32)))
            return last_x, caches
        return prefill

    def _build_admit_sample(self):
        """First-token sample on the prefill hidden states (TTFT): the
        per-slot logit controls apply at nt=0 for the admitted rows;
        non-admitted rows' outputs are discarded by the host."""
        head_logits = self._core().head_logits
        rep_on = self._rep_on
        do_sample = self.do_sample
        top_k, top_p, temp = self.top_k, self.top_p, self.temperature

        def admit_sample(h_arrays, last_x, seeds, eos_ids, min_len,
                         rep_pen, presence):
            logits = head_logits(h_arrays, last_x)
            logits = logits.reshape(logits.shape[0], -1)
            nt0 = jnp.zeros(logits.shape[0], jnp.int32)
            logits = _penalize_slots(
                logits, presence if rep_on else None, rep_pen, nt0,
                min_len, eos_ids)
            return _sample_rows(logits, do_sample, top_k, top_p, temp,
                                seeds, nt0)
        return admit_sample

    def _build_bulk_admit(self, sb):
        """In-slot BULK prefill: one causal-flash pass over a single
        padded prompt row [1, sb] (parallel over positions — no scan),
        then one scatter of its K/V into the slot's cache row. Garbage
        K/V at padded positions [plen, sb) is safe: decode writes the
        real token's K/V at position `lens` BEFORE attending it
        (write-then-attend), so a garbage position is always overwritten
        the step it would first become attendable."""
        core = self._core()
        bulk_hidden = core.bulk_hidden
        int8 = self.dec._int8_cache()
        cache_dtype = self.dec.fmt.qkv_weights[0]._data.dtype

        def bulk_admit(stk, e_arrays, caches, toks, slot, plen):
            x, kv_all = bulk_hidden(stk, e_arrays, toks)
            # the row's OWN last real token's hidden state (ragged pad)
            last = jax.lax.dynamic_slice_in_dim(x, plen - 1, 1, 1)
            kv = kv_all[:, :, 0]                      # [L, 2, H, sb, D]
            if isinstance(caches, dict):
                # paged: scatter the prompt's K/V through the slot's
                # block table. Positions >= plen (the pow-2 pad) go OUT
                # OF BOUNDS and drop — unlike the dense path they never
                # land as garbage, so the pad needs no pool blocks and
                # the write-then-attend overwrite argument isn't even
                # needed.
                pool_kv, tbl = caches["kv"], caches["tbl"]
                nb = pool_kv.shape[2]
                bt = pool_kv.shape[4]
                row = jax.lax.dynamic_index_in_dim(tbl, slot, 0,
                                                   keepdims=False)
                pos = jnp.arange(sb, dtype=jnp.int32)
                blk = jnp.where(pos < plen, jnp.take(row, pos // bt), nb)
                off = pos % bt
                if int8:
                    qi, sc = _absmax_int8(kv, -1)
                    kvq = pool_kv.at[:, :, blk, :, off, :].set(
                        jnp.transpose(qi, (3, 0, 1, 2, 4)), mode="drop")
                    scq = caches["sc"].at[:, :, blk, :, 0, off].set(
                        jnp.transpose(sc[..., 0], (3, 0, 1, 2)),
                        mode="drop")
                    caches = dict(caches, kv=kvq, sc=scq)
                else:
                    caches = dict(caches, kv=pool_kv.at[
                        :, :, blk, :, off, :].set(
                        jnp.transpose(kv, (3, 0, 1, 2, 4)).astype(
                            pool_kv.dtype), mode="drop"))
            elif int8:
                qi, sc = _absmax_int8(kv, -1)
                ci8 = caches[0].at[:, :, slot, :, :sb, :].set(qi)
                scs = caches[1].at[:, :, slot, :, 0, :sb].set(sc[..., 0])
                caches = (ci8, scs)
            else:
                caches = caches.at[:, :, slot, :, :sb, :].set(
                    kv.astype(cache_dtype))
            return caches, last
        return bulk_admit

    def _bulk_admit_row(self, stk, e_arrays, req, last_x):
        plen = req.prompt.size
        sb = min(1 << (int(plen) - 1).bit_length(), self.smax)
        toks = np.zeros((1, sb), np.int32)
        toks[0, :plen] = req.prompt
        (out, row_x), _ = self._run_dispatch(
            ("bulk_admit", sb),
            lambda s=sb: self._build_bulk_admit(s), (2,),
            (stk, e_arrays, self._cache_arg(), jnp.asarray(toks),
             jnp.asarray(req.slot, jnp.int32),
             jnp.asarray(plen, jnp.int32)),
            rows=1, tokens=int(plen))
        self._keep_caches(out)
        return last_x.at[req.slot].set(row_x[0])

    # --------------------------------------------------------- scheduling
    def _free_slots(self):
        return [i for i in range(self.num_slots)
                if not self._active[i] and self._slot_req[i] is None]

    def _prefix_cache_for_dispatch(self):
        """The prefix cache this dispatch may use, or None. The PAGED
        cache is pure host index bookkeeping over the (head-sharded)
        block pool — adopt writes table entries, commit pins the
        slot's own blocks — so it participates under a mesh unchanged.
        The DENSE cache's compiled adopt/commit gather/splat copies
        assume an unsharded ring: that is the one genuinely
        unsupported config left, so under a mesh it stays off (warned
        ONCE, naming why) and every admission counts as a miss —
        hits + misses == admitted still reconciles and the dead cache
        is visible as hit_rate == 0."""
        if self.prefix_cache is None:
            return None
        if self.paged or self.dec._mesh_mp() is None:
            return self.prefix_cache
        if not self._pc_mesh_warned:
            import warnings
            warnings.warn(
                "serving: dense prefix cache disabled under an active "
                "mp mesh — its compiled adopt/commit copies assume an "
                "unsharded ring cache, so every admission counts as a "
                "miss. The paged engine (the default) shards its pool "
                "by head and keeps prefix caching on under a mesh.",
                RuntimeWarning, stacklevel=3)
            self._pc_mesh_warned = True
        return None

    def _admit(self):
        """Move queued requests into free slots: batched in-slot prefill
        (chunked, write-masked) + one first-token sample. Returns the
        list of admitted requests (each just emitted its first token)."""
        free = self._free_slots()
        batch = []
        while free and self._queue_len():
            if self.paged:
                # pool-bounded admission: a request enters a slot only
                # with its WORST-CASE block reservation covered (sum of
                # running reservations <= NBtotal keeps every lazy
                # allocation satisfiable — shared blocks only add
                # slack). Otherwise it waits; eviction frees blocks.
                head = self._queue_head()
                need = self._blocks_needed(head.prompt.size,
                                           head.max_new_tokens)
                if self._kv_reserved + need > self.pool.num_blocks:
                    break
                self._kv_reserved += need
            req = self._queue_popleft()
            slot = free.pop(0)
            req.slot = slot
            req.state = "running"
            self._slot_req[slot] = req
            batch.append(req)
        if not batch:
            return []
        self._admitted += len(batch)
        for r in batch:
            self._class_admitted[r.priority] += 1
        tele = self.telemetry
        # t_admit is ALWAYS stamped (ring on or off): the SLO layer's
        # queue/service decomposition reads it at _finish
        t_adm = self.clock()
        for r in batch:
            r.t_admit = t_adm
            tele.req_admitted(r.rid, r.slot, t_adm)
        b = self.num_slots
        stk = self.dec._stacked()
        e_arrays = [p._data for p in self.dec._embed_params]
        h_arrays = self.dec._maybe_quant_head(
            [p._data for p in self.dec._head_params])

        if self._rep_on:
            # reset the admitted rows' presence to their prompt one-hots
            vocab = self._presence_init().shape[1]
            admit_mask = np.zeros(b, bool)
            rows = np.zeros((b, vocab), bool)
            for r in batch:
                admit_mask[r.slot] = True
                rows[r.slot, r.prompt] = True
            self._presence = jnp.where(
                jnp.asarray(admit_mask)[:, None], jnp.asarray(rows),
                self._presence_init())

        # E from the embedding table; dtype from the stack
        e_dim = int(e_arrays[0].shape[-1]) if e_arrays else \
            int(self.dec.fmt.qkv_weights[0]._data.shape[-1])
        dt = self.dec.fmt.qkv_weights[0]._data.dtype
        last_x = jnp.zeros((b, 1, e_dim), dt)

        # Two in-slot prefill flavors:
        #  * bulk (default, no mesh): ONE causal-flash pass over the
        #    single admitted row, padded to a pow-2 bucket, then one
        #    scatter into that slot's cache row. Prefill compute is per
        #    ROW — the masked batch scan below runs every step over all
        #    B rows to fill one, which made admission cost ~B x static
        #    batching's shared prefill on the serving bench.
        #  * masked scan (mesh / opt-out PADDLE_TPU_SERVE_BULK=0): the
        #    chunked prefill scan with a per-row write mask.
        mesh_on = self.dec._mesh_mp() is not None
        use_bulk = (not mesh_on and
                    os.environ.get("PADDLE_TPU_SERVE_BULK", "1") != "0")
        # Prefix-cache admission: the longest published block chain is
        # splatted into the slot's cache row by ONE compiled gather-copy
        # dispatch (pow-2 ladder over chain length), and only the
        # uncached suffix goes through prefill. The paged cache (host
        # index writes over the shared pool) also runs under a mesh;
        # only the dense flavor sits out there — see
        # _prefix_cache_for_dispatch for the miss-counting contract.
        pc = self._prefix_cache_for_dispatch()
        if pc is None and self.prefix_cache is not None:
            self._prefix_misses += len(batch)
        base = np.zeros(b, np.int32)          # adopted tokens per slot
        published = set()                     # slots published this admit
        for r in batch:
            if pc is not None:
                # lookup + (miss-path bulk prefill + publish) run PER
                # REQUEST, in order: a cold gang of same-template
                # requests admitted in one batch would otherwise ALL
                # miss — row 1's publish lets rows 2..B adopt the
                # template inside the same admission
                nodes = pc.lookup(r.prompt)
                if nodes:
                    if self.paged:
                        # THE zero-copy hit: the matched chain's pool
                        # indices are written into the slot's block
                        # table (+refcount) — no gather, no dispatch
                        base[r.slot] = pc.adopt_into(self._tables,
                                                     r.slot, nodes)
                    else:
                        pc.store.acquire(nodes)   # pin across the copy
                        try:
                            self._caches = pc.adopt(self._caches,
                                                    r.slot, nodes)
                        finally:
                            pc.store.release(nodes)
                        base[r.slot] = len(nodes) * pc.block_tokens
                    self._prefix_hits += 1
                    self._prefill_tokens_saved += int(base[r.slot])
                    tele.req_event(r.rid, "prefix_adopt", t_adm)
                else:
                    self._prefix_misses += 1
            if self.prefix_cache is not None:
                self._prefill_tokens_computed += (r.prompt.size
                                                  - int(base[r.slot]))
            if self.paged:
                # map the prompt's remaining blocks (adopted entries
                # already point into the pool); the decode window maps
                # lazily chunk by chunk
                self._map_blocks(r.slot, r.prompt.size)
            if use_bulk and not base[r.slot]:
                last_x = self._bulk_admit_row(stk, e_arrays, r, last_x)
                tele.req_event(r.rid, "prefill_chunk", t_adm)
                if pc is not None:
                    if self.paged:
                        pc.publish_from(self._tables, r.slot, r.prompt)
                    else:
                        pc.publish(self._caches, r.slot, r.prompt)
                    published.add(r.slot)
        # a prefix hit always takes the masked-scan path for its suffix:
        # the bulk flash pass has no way to attend the adopted prefix
        # K/V, while the per-token scan attends the whole cache row up
        # to each position by construction
        scan_batch = [r for r in batch if not use_bulk or base[r.slot]]
        if scan_batch:
            maxp = max(r.prompt.size - int(base[r.slot])
                       for r in scan_batch)
            chunks = self._prefill_chunks(maxp)
            prompts = np.zeros((b, sum(chunks)), np.int32)
            n_left = np.zeros(b, np.int32)
            for r in scan_batch:
                sfx = r.prompt[int(base[r.slot]):]
                prompts[r.slot, :sfx.size] = sfx
                n_left[r.slot] = sfx.size
            pos = 0
            for chunk in chunks:
                toks = jnp.asarray(
                    np.ascontiguousarray(prompts[:, pos:pos + chunk].T))
                t0 = np.where(n_left > 0, base + pos, self._lens).astype(
                    np.int32)
                n_valid = np.clip(n_left - pos, 0, chunk).astype(
                    np.int32)
                (last_x, out), _ = self._run_dispatch(
                    ("prefill", chunk),
                    lambda c=chunk: self._build_prefill_chunk(c), (2,),
                    (stk, e_arrays, self._cache_arg(), toks,
                     jnp.asarray(t0), jnp.asarray(n_valid), last_x),
                    rows=int((n_valid > 0).sum()),
                    tokens=int(n_valid.sum()))
                self._keep_caches(out)
                pos += chunk
            for r in scan_batch:
                self.telemetry.req_event(r.rid, "prefill_chunk", t_adm)
        # commit-on-prefill for the rows whose prefill just landed via
        # the scan (bulk-miss rows published inline above): publish each
        # prompt's full blocks back to the pool under their token keys.
        # Adopted blocks re-resolve to their existing nodes (dedup, no
        # copy); only genuinely new blocks are copied out of the slot
        # row (dense) or referenced in place (paged: publication takes
        # a store ref on the slot's OWN blocks — zero-copy commit).
        # COW is structural either way: decode only writes slot-private
        # positions >= plen, strictly past every published full block.
        if pc is not None:
            for r in batch:
                if r.slot not in published:
                    if self.paged:
                        pc.publish_from(self._tables, r.slot, r.prompt)
                    else:
                        pc.publish(self._caches, r.slot, r.prompt)

        # per-slot params refresh for the admitted rows
        for r in batch:
            s = r.slot
            self._lens[s] = r.prompt.size
            self._nt[s] = 0
            self._max_nt[s] = r.max_new_tokens
            self._eos[s] = (-1 if r.eos_token_id is None
                            else int(r.eos_token_id))
            self._min_len[s] = r.min_length
            self._rep_pen[s] = r.repetition_penalty
            self._rseed[s] = r.seed
            if self._drafters is not None:
                self._drafters[s].reset(r.prompt)

        out, _ = self._run_dispatch(
            ("admit_sample",), self._build_admit_sample, (),
            (h_arrays, last_x, jnp.asarray(self._rseed, jnp.int32),
             jnp.asarray(self._eos), jnp.asarray(self._min_len),
             jnp.asarray(self._rep_pen), self._presence_arg()),
            rows=len(batch), tokens=len(batch))
        nxt = np.asarray(out)

        now = self.clock()
        self._decode_steps += len(batch)     # one sample event per row
        for r in batch:
            s = r.slot
            tok0 = int(nxt[s])
            r.t_first = now
            tele.req_event(r.rid, "first_token", now)
            r.tokens.append(tok0)
            self._class_tokens[r.priority] += 1
            self._nt[s] = 1
            self._tok[s] = tok0
            if self._drafters is not None:
                self._drafters[s].update([tok0])
            hit_eos = (r.eos_token_id is not None
                       and tok0 == int(r.eos_token_id))
            self._active[s] = not hit_eos and r.max_new_tokens > 1
            if self._rep_on:
                self._presence = self._presence.at[s, tok0].set(True)
            if not self._active[s]:
                self._finish(r, now)
        return batch

    def _admit_chunked(self):
        """Token-budget admission: move queued requests into free slots
        as pure BOOKKEEPING — prefix-cache lookup/adopt plus slot-state
        reset. No prefill dispatch happens here: the slot enters
        `prefilling` (pf_left > 0) and the budget packer streams its
        prompt through spare step capacity, so a long prompt can never
        stall the decode gang. Publication back to the prefix store
        happens when the prompt completes (commit-on-prefill, the same
        dedup as the phase path — cold same-template gangs admitted
        together all miss, unlike phase admission's serialized
        publish-then-lookup; the store converges one prompt later)."""
        free = self._free_slots()
        batch = []
        while free and self._queue_len():
            if self.paged:
                # pool-bounded admission, same reservation rule as the
                # phase path: worst-case blocks covered or the head
                # waits (deadline expiry still runs every step)
                head = self._queue_head()
                need = self._blocks_needed(head.prompt.size,
                                           head.max_new_tokens)
                if self._kv_reserved + need > self.pool.num_blocks:
                    break
                self._kv_reserved += need
            req = self._queue_popleft()
            slot = free.pop(0)
            req.slot = slot
            req.state = "running"
            self._slot_req[slot] = req
            batch.append(req)
        if not batch:
            return []
        self._admitted += len(batch)
        for r in batch:
            self._class_admitted[r.priority] += 1
        tele = self.telemetry
        # always stamped (SLO queue/service decomposition reads it)
        t_adm = self.clock()
        for r in batch:
            r.t_admit = t_adm
            tele.req_admitted(r.rid, r.slot, t_adm)
        if self._rep_on:
            # presence seeds with the FULL prompt at admission (the
            # budget core's penalty at the first-token sample needs it;
            # teacher-forced prefill columns never consume it)
            vocab = self._presence_init().shape[1]
            admit_mask = np.zeros(self.num_slots, bool)
            rows = np.zeros((self.num_slots, vocab), bool)
            for r in batch:
                admit_mask[r.slot] = True
                rows[r.slot, r.prompt] = True
            self._presence = jnp.where(
                jnp.asarray(admit_mask)[:, None], jnp.asarray(rows),
                self._presence_init())
        pc = self._prefix_cache_for_dispatch()
        if pc is None and self.prefix_cache is not None:
            self._prefix_misses += len(batch)
        for r in batch:
            s = r.slot
            base = 0
            if pc is not None:
                nodes = pc.lookup(r.prompt)
                if nodes:
                    if self.paged:
                        base = pc.adopt_into(self._tables, s, nodes)
                    else:
                        pc.store.acquire(nodes)   # pin across the copy
                        try:
                            self._caches = pc.adopt(self._caches, s,
                                                    nodes)
                        finally:
                            pc.store.release(nodes)
                        base = len(nodes) * pc.block_tokens
                    self._prefix_hits += 1
                    self._prefill_tokens_saved += int(base)
                    tele.req_event(r.rid, "prefix_adopt", t_adm)
                else:
                    self._prefix_misses += 1
            if self.prefix_cache is not None:
                self._prefill_tokens_computed += (r.prompt.size
                                                  - int(base))
            # lens IS the prefill cursor: KV entries written so far
            # (adopted prefix now, streamed chunks as they land)
            self._lens[s] = base
            self._pf_left[s] = r.prompt.size - int(base)
            self._nt[s] = 0
            self._max_nt[s] = r.max_new_tokens
            self._eos[s] = (-1 if r.eos_token_id is None
                            else int(r.eos_token_id))
            self._min_len[s] = r.min_length
            self._rep_pen[s] = r.repetition_penalty
            self._rseed[s] = r.seed
            self._active[s] = False          # decoding starts at finish
            if self._drafters is not None:
                self._drafters[s].reset(r.prompt)
        return batch

    def _get_spec_rng(self):
        if self._spec_rng is None:
            self._spec_rng = np.random.RandomState(
                _host_seed(next_key()))
        return self._spec_rng

    def _budget_step(self):
        """ONE token-budget dispatch: pack decode rows (1 mandatory
        input token + any draft claim each) and prefill chunks into the
        compiled [B, C] budget core, then harvest per-row. Pure-decode
        steps fall back to the (equally warm) decode-chunk scan when
        IT moves more tokens per dispatch — the budget arithmetic that
        subsumes the deprecated thin-draft heuristic. Returns tokens
        emitted. Flat mode (PADDLE_SERVING_FLAT_BUDGET) swaps the
        [B, C] block for the token-flattened [T] stream — same
        contracts, ~zero padding (see _flat_budget_step)."""
        if self._flat_budget:
            return self._flat_budget_step()
        from .spec_decode import propose_claims
        b = self.num_slots
        c = self._budget_cols
        dec_rows = [s for s in range(b) if self._active[s]]
        pf_rows = [s for s in range(b) if self._pf_left[s] > 0]
        if not dec_rows and not pf_rows:
            return 0
        k = self.spec_k
        if k:
            # a row's whole segment (input + drafts) must fit the C
            # columns; the bonus-token budget cap lives in the helper
            drafts, dlen = propose_claims(self._drafters, dec_rows, k,
                                          self._max_nt - self._nt,
                                          col_cap=c)
        else:
            drafts = np.zeros((b, 1), np.int32)
            dlen = np.zeros(b, np.int32)
        if not pf_rows and len(dec_rows) + int(dlen.sum()) < \
                len(dec_rows) * self.decode_chunk:
            # budget arithmetic: the block step processes
            # len(dec) + sum(dlen) real tokens, the chunk scan
            # len(dec) * decode_chunk — dispatch whichever moves more
            return self._decode_one_chunk()
        # ---- pack: decode inputs are mandatory, prefill chunks fill
        # spare capacity (rotating start so concurrent prefills share
        # the budget), drafts claim what is left
        budget = self.token_budget - len(dec_rows)
        toks = np.zeros((b, c), np.int32)
        seg = np.zeros(b, np.int32)
        gen0 = np.full(b, c, np.int32)
        pf_n = np.zeros(b, np.int32)
        for s in dec_rows:
            toks[s, 0] = self._tok[s]
            seg[s] = 1
            gen0[s] = 0
        if pf_rows:
            # weighted-fair packing: each QoS class PRESENT in the
            # prefilling set gets its proportional share of the spare
            # budget, spent FCFS (Sarathi's order) within the class,
            # leftovers spill work-conserving in class order. With one
            # class present this is exactly the old pure-FCFS packing —
            # the oldest prompt takes the whole spare budget first
            # (round-robin would stretch every concurrent TTFT tail).
            allocs, budget = self._prefill_allocations(pf_rows, budget,
                                                       col_cap=c)
            for s, n in allocs:
                req = self._slot_req[s]
                p0 = req.prompt.size - int(self._pf_left[s])
                toks[s, :n] = req.prompt[p0:p0 + n]
                seg[s] = n
                pf_n[s] = n
                if n == int(self._pf_left[s]):
                    # finishing this dispatch: the last prompt token's
                    # logits sample the request's FIRST generated token
                    gen0[s] = n - 1
        if k:
            for s in dec_rows:
                m = min(int(dlen[s]), budget)
                dlen[s] = m
                if m > 0:
                    toks[s, 1:1 + m] = drafts[s, :m]
                    seg[s] = 1 + m
                    budget -= m
        tail = 0 if k else max(self.decode_chunk - 1, 0)
        if self.paged:
            # cover every packed row's write window before dispatch
            # (lazy mapping + the COW guard): the block's segment,
            # plus the trailing decode scan's window for rows that
            # will be decoding after the block (active rows and
            # prefill rows finishing here), clamped to the
            # admission-time reservation `plen + max_new`
            for s in range(b):
                if not seg[s]:
                    continue
                decodes = bool(self._active[s]) or \
                    (pf_n[s] and pf_n[s] == self._pf_left[s])
                hi = (int(self._lens[s]) + int(seg[s])
                      + (tail if decodes else 0))
                req = self._slot_req[s]
                cap_pos = req.prompt.size + int(self._max_nt[s])
                self._ensure_writable(s, int(self._lens[s]),
                                      min(hi, cap_pos))
        stk = self.dec._stacked()
        e_arrays = [p._data for p in self.dec._embed_params]
        h_arrays = self.dec._maybe_quant_head(
            [p._data for p in self.dec._head_params])
        full_logits = bool(self.do_sample and k)
        res, ev = self._run_dispatch(
            ("budget", c),
            lambda: self.dec._build_budget_core(
                c, self._rep_on, self.do_sample, self.top_k, self.top_p,
                self.temperature, full_logits=full_logits,
                chain=bool(k), scan_tail=tail),
            (3,),
            (stk, e_arrays, h_arrays, self._cache_arg(),
             jnp.asarray(toks), jnp.asarray(self._lens),
             jnp.asarray(seg), jnp.asarray(gen0), jnp.asarray(self._nt),
             jnp.asarray(self._max_nt), jnp.asarray(self._eos),
             jnp.asarray(self._min_len), jnp.asarray(self._rep_pen),
             self._presence_arg(), jnp.asarray(self._rseed, jnp.int32)),
            rows=int((seg > 0).sum()),
            budget_used=int(seg.sum()),
            budget_wasted=b * c - int(seg.sum()),
            drafts=int(dlen.sum()))
        self._keep_caches(res[0])
        self._budget_steps += 1
        self._budget_tokens_used += int(seg.sum())
        self._budget_prefill_tokens += int(pf_n.sum())
        self._budget_decode_tokens += len(dec_rows)
        self._budget_draft_tokens += int(dlen.sum())
        # the row layout COMPUTES every one of the B x C positions —
        # the masked remainder is the wasted-FLOPs ledger the flat
        # layout drives to ~0
        self._budget_padding_tokens += b * c - int(seg.sum())
        if not k:
            return self._harvest_budget_plain(res, ev, pf_n, tail)
        # per-slot chain views into the [B, C] block outputs: slot s's
        # segment occupies columns [0, seg[s]) of its row
        out = np.asarray(res[1])
        if full_logits:
            out = out.astype(np.float32)
        chain_out = {s: out[s, :int(seg[s])]
                     for s in range(b) if seg[s]}
        return self._harvest_budget_chain(chain_out, ev, pf_n, dec_rows,
                                          drafts, dlen, full_logits)

    def _harvest_budget_plain(self, res, ev, pf_n, tail):
        """Non-spec budget harvest, shared by the row-aligned and flat
        dispatches (both cores return the same advanced-state tuple):
        the core advanced ALL row state on device (block sample +
        trailing decode scan); the host walks tokens and finish
        events. Returns tokens emitted."""
        b = self.num_slots
        tele = self.telemetry
        now = self.clock()
        pc = self._prefix_cache_for_dispatch()
        (_, tok0, emit0, (ys_t, ys_e), tokc, lensc, activec, ntc,
         presc) = res
        tok0 = np.asarray(tok0)
        emit0 = np.asarray(emit0)
        ys_t = np.asarray(ys_t)          # [tail, B]
        ys_e = np.asarray(ys_e)
        prev_active = self._active.copy()
        self._tok = np.array(tokc)
        self._lens = np.array(lensc)
        self._nt = np.array(ntc)
        still_active = np.array(activec)
        if self._rep_on:
            self._presence = presc
        n_emitted = 0
        for s in range(b):
            req = self._slot_req[s]
            if req is None:
                continue
            if pf_n[s]:
                self._pf_left[s] -= int(pf_n[s])
                tele.req_event(req.rid, "prefill_chunk", now)
                if self._pf_left[s] == 0 and pc is not None:
                    # commit-on-prefill publication: decode writes
                    # (including this dispatch's trailing scan)
                    # land strictly past every published full
                    # block, so publishing at harvest is safe
                    if self.paged:
                        pc.publish_from(self._tables, s, req.prompt)
                    else:
                        pc.publish(self._caches, s, req.prompt)
            if not emit0[s] and not prev_active[s]:
                continue                 # idle or still prefilling
            row_toks = []
            if emit0[s]:
                row_toks.append(int(tok0[s]))
                if pf_n[s]:              # the prompt finished HERE
                    req.t_first = now
                    tele.req_event(req.rid, "first_token", now)
            if tail:
                hits = ys_e[:, s]
                row_toks.extend(int(t) for t in ys_t[hits, s])
            if row_toks and prev_active[s]:
                tele.req_event(req.rid, "decode", now)
            req.tokens.extend(row_toks)
            self._class_tokens[req.priority] += len(row_toks)
            n_emitted += len(row_toks)
            self._decode_steps += len(row_toks)
            if not still_active[s]:
                self._finish(req, now)
        self._active = still_active
        tele.finish_step(ev, self.clock() if ev is not None else 0.0,
                         tokens=n_emitted)
        return n_emitted

    def _harvest_budget_chain(self, chain_out, ev, pf_n, dec_rows,
                              drafts, dlen, full_logits):
        """Spec budget harvest, shared by the row-aligned and flat
        dispatches: block-only (accepted drafts already make the step
        multi-token); acceptance/rollback on host, as in the legacy
        verify step. ``chain_out`` maps each packed slot to ITS
        segment's outputs — argmax chain [seg] or penalized logits
        [seg, V] — so the two layouts' different block shapes never
        leak into the acceptance logic. Returns tokens emitted."""
        from .spec_decode import (filtered_probs, greedy_accept,
                                  rejection_sample, truncate_emitted)
        tele = self.telemetry
        now = self.clock()
        pc = self._prefix_cache_for_dispatch()
        n_emitted = 0
        new_rows, new_cols = [], []
        # FCFS (rid) order, exactly the packer's: publication order
        # into the bounded prefix store is part of its eviction state
        pf_order = sorted((s for s in range(self.num_slots) if pf_n[s]),
                          key=lambda s: self._slot_req[s].rid)
        for s in pf_order:
            n = int(pf_n[s])
            req = self._slot_req[s]
            self._pf_left[s] -= n
            self._lens[s] += n
            tele.req_event(req.rid, "prefill_chunk", now)
            if self._pf_left[s] > 0:
                continue
            # prompt complete: commit-on-prefill publication, then the
            # first token (TTFT is measured to exactly this event)
            if pc is not None:
                if self.paged:
                    pc.publish_from(self._tables, s, req.prompt)
                else:
                    pc.publish(self._caches, s, req.prompt)
            arr = chain_out[s]
            if full_logits:
                p = filtered_probs(arr[-1][None], self.top_k,
                                   self.top_p, self.temperature)
                tok0 = int(self._get_spec_rng().choice(p.shape[-1],
                                                       p=p[0]))
            else:
                tok0 = int(arr[-1])                   # greedy chain
            req.t_first = now
            tele.req_event(req.rid, "first_token", now)
            req.tokens.append(tok0)
            self._class_tokens[req.priority] += 1
            self._nt[s] = 1
            self._tok[s] = tok0
            self._decode_steps += 1      # one sample event for the row
            n_emitted += 1
            if self._drafters is not None:
                self._drafters[s].update([tok0])
            if self._rep_on:
                new_rows.append(s)
                new_cols.append(tok0)
            hit_eos = (req.eos_token_id is not None
                       and tok0 == int(req.eos_token_id))
            self._active[s] = not hit_eos and req.max_new_tokens > 1
            if not self._active[s]:
                self._finish(req, now)
        for s in dec_rows:
            req = self._slot_req[s]
            if req is None or not self._active[s]:
                continue
            m = int(dlen[s])
            arr = chain_out[s]
            if full_logits:
                probs = filtered_probs(arr[:m + 1], self.top_k,
                                       self.top_p, self.temperature)
                kept, _ = rejection_sample(drafts[s, :m], probs,
                                           self._get_spec_rng())
            else:
                kept, _ = greedy_accept(drafts[s, :m], arr[:m + 1])
            eos = None if self._eos[s] < 0 else int(self._eos[s])
            emitted, hit_eos = truncate_emitted(
                kept, int(self._max_nt[s] - self._nt[s]), eos)
            self._nt[s] += len(emitted)
            req.tokens.extend(emitted)
            self._class_tokens[req.priority] += len(emitted)
            n_emitted += len(emitted)
            self._lens[s] += len(emitted)
            self._tok[s] = emitted[-1]
            self._decode_steps += 1
            self._draft_proposed += m
            self._draft_accepted += len(emitted) - 1
            tele.req_event(req.rid, "verify", now)
            if self._drafters is not None:
                self._drafters[s].update(emitted)
            if self._rep_on:
                new_rows.extend([s] * len(emitted))
                new_cols.extend(emitted)
            if hit_eos or self._nt[s] >= self._max_nt[s]:
                self._active[s] = False
                self._finish(req, now)
        if self._rep_on and new_rows:
            # the budget core's speculative presence was discarded —
            # only tokens that actually landed join the carry
            self._presence = self._presence.at[
                jnp.asarray(new_rows), jnp.asarray(new_cols)].set(True)
        tele.finish_step(ev, self.clock() if ev is not None else 0.0,
                         tokens=n_emitted)
        return n_emitted

    def _flat_budget_step(self):
        """ONE token-FLATTENED budget dispatch (the Sarathi
        token-flattened batch, PADDLE_SERVING_FLAT_BUDGET): instead of
        the [B, C] row-aligned block, the packer emits ONE ragged [T]
        stream — a B-wide DECODE REGION (token i is slot i's input when
        it decodes draft-free; idle slots ride the sentinel) followed
        by SEGMENTS (spec claims, prefill chunks) packed back-to-back
        with starts aligned to the flat kernel's chunk size, total
        segment width from an eighth-octave ladder. Per-token
        (slot, pos) index
        vectors drive the compiled flat core
        (generation._build_flat_budget_core); a prefill segment can
        span the whole spare budget (no C cap), so long prompts stream
        budget-sized chunks and budget_padding_tokens stays ~0 where
        the row layout computed (B-1) x C masked positions. All stream
        layout is DATA — only the ladder width is trace structure, so
        churn retraces nothing once the ladder is warm. Token outputs
        are EXACTLY the row dispatch's (shared harvests, shared
        sampling keyed fold_in(seed, nt)). Returns tokens emitted."""
        from ..ops.pallas.decode_attention import FLAT_CHUNK
        from .spec_decode import propose_claims
        b = self.num_slots
        dec_rows = [s for s in range(b) if self._active[s]]
        pf_rows = [s for s in range(b) if self._pf_left[s] > 0]
        if not dec_rows and not pf_rows:
            return 0
        k = self.spec_k
        if k:
            drafts, dlen = propose_claims(self._drafters, dec_rows, k,
                                          self._max_nt - self._nt)
        else:
            drafts = np.zeros((b, 1), np.int32)
            dlen = np.zeros(b, np.int32)
        if not pf_rows and len(dec_rows) + int(dlen.sum()) < \
                len(dec_rows) * self.decode_chunk:
            # same budget arithmetic as the row dispatch: pure-decode
            # steps run whichever warm executable moves more tokens
            return self._decode_one_chunk()
        # ---- pack: decode inputs are mandatory; prefill chunks (FCFS,
        # uncapped by any column count) fill spare capacity FIRST and
        # drafts claim what is left — the row packer's priority order,
        # so saturated decoders with fat drafts can never starve a
        # pending prefill (TTFT) in flat mode either
        budget = self.token_budget - len(dec_rows)
        segs = []                    # [slot, tokens, is_decode_claim]
        pf_n = np.zeros(b, np.int64)
        if pf_rows:
            # weighted-fair packing, same allocator as the row path
            # (FCFS within a class; single-class == old pure FCFS) —
            # no column cap, so a segment can span the whole share
            allocs, budget = self._prefill_allocations(pf_rows, budget)
            for s, n in allocs:
                req = self._slot_req[s]
                p0 = req.prompt.size - int(self._pf_left[s])
                segs.append([s, req.prompt[p0:p0 + n].astype(np.int32),
                             False])
                pf_n[s] = n
        if k:
            for s in dec_rows:
                m = min(int(dlen[s]), budget)
                dlen[s] = m
                if m > 0:
                    segs.append([s, np.concatenate(
                        ([self._tok[s]], drafts[s, :m])).astype(
                        np.int32), True])
                    budget -= m
        regd = [s for s in dec_rows if not (k and dlen[s] > 0)]
        # ---- layout: segment starts aligned to FLAT_CHUNK (the flat
        # kernel's single-slot query-chunk contract), total segment
        # width from an EIGHTH-OCTAVE ladder: round up to the next
        # multiple of next_pow2(need)/8 — ladder tail <= ~12% of the
        # stream (a plain pow-2 ladder wasted up to 2x on long prompt
        # chunks, re-creating a chunk of the row padding this layout
        # exists to kill) at <= 8 widths per octave, all bounded by
        # the token budget; the width is the ONLY trace structure
        align = FLAT_CHUNK
        starts = []
        cursor = 0
        for e in segs:
            starts.append(cursor)
            cursor = -(-(cursor + len(e[1])) // align) * align
        if segs:
            need = max(cursor, align)
            step = max((1 << (need - 1).bit_length()) // 8, align)
            ts = -(-need // step) * step
        else:
            ts = 0
        t_total = b + ts
        nc = ts // align
        toks = np.zeros(t_total, np.int32)
        tslot = np.full(t_total, b, np.int32)       # b == pad sentinel
        tpos = np.zeros(t_total, np.int32)
        tcol = np.zeros(t_total, np.int32)
        tstart = np.zeros(t_total, np.int32)
        cslot = np.zeros(nc, np.int32)
        cbase = np.zeros(nc, np.int32)
        cn = np.zeros(nc, np.int32)
        last_idx = np.zeros(b, np.int32)
        emit0 = np.zeros(b, bool)
        adv = np.zeros(b, np.int32)
        gen0 = np.zeros(b, np.int32)
        for s in regd:
            toks[s] = self._tok[s]
            tslot[s] = s
            tpos[s] = self._lens[s]
            tstart[s] = s
            last_idx[s] = s
            emit0[s] = True
            adv[s] = 1
        for e, st in zip(segs, starts):
            s, tk, is_dec = e
            n = len(tk)
            sl = slice(b + st, b + st + n)
            base = int(self._lens[s])
            toks[sl] = tk
            tslot[sl] = s
            tpos[sl] = base + np.arange(n)
            tcol[sl] = np.arange(n)
            tstart[sl] = b + st
            last_idx[s] = b + st + n - 1
            adv[s] = n
            if is_dec:
                emit0[s] = True
            else:
                fin = pf_n[s] == self._pf_left[s]
                emit0[s] = bool(fin)
                # the last prompt token's logits sample the request's
                # FIRST generated token; mid-prompt chunks never emit
                gen0[s] = n - 1 if fin else (1 << 30)
            for ci in range(st // align, (st + n - 1) // align + 1):
                cslot[ci] = s
                cbase[ci] = base + (ci * align - st)
                cn[ci] = min(n - (ci * align - st), align)
        used = len(regd) + sum(len(e[1]) for e in segs)
        computed = t_total
        tail = 0 if k else max(self.decode_chunk - 1, 0)
        if self.paged:
            # cover every packed slot's write window before dispatch
            # (lazy mapping + the COW guard), clamped to the
            # admission-time reservation — same rule as the row path
            for s in range(b):
                if not adv[s]:
                    continue
                decodes = bool(self._active[s]) or \
                    (pf_n[s] and pf_n[s] == self._pf_left[s])
                hi = (int(self._lens[s]) + int(adv[s])
                      + (tail if decodes else 0))
                req = self._slot_req[s]
                cap_pos = req.prompt.size + int(self._max_nt[s])
                self._ensure_writable(s, int(self._lens[s]),
                                      min(hi, cap_pos))
        stk = self.dec._stacked()
        e_arrays = [p._data for p in self.dec._embed_params]
        h_arrays = self.dec._maybe_quant_head(
            [p._data for p in self.dec._head_params])
        full_logits = bool(self.do_sample and k)
        res, ev = self._run_dispatch(
            ("flat_budget", ts),
            lambda: self.dec._build_flat_budget_core(
                ts, b, self._rep_on, self.do_sample, self.top_k,
                self.top_p, self.temperature, full_logits=full_logits,
                chain=bool(k), scan_tail=tail),
            (3,),
            (stk, e_arrays, h_arrays, self._cache_arg(),
             jnp.asarray(toks), jnp.asarray(tslot), jnp.asarray(tpos),
             jnp.asarray(cslot), jnp.asarray(cbase), jnp.asarray(cn),
             jnp.asarray(tcol), jnp.asarray(tstart), jnp.asarray(gen0),
             jnp.asarray(self._tok), jnp.asarray(last_idx),
             jnp.asarray(emit0), jnp.asarray(adv),
             jnp.asarray(self._lens), jnp.asarray(self._nt),
             jnp.asarray(self._max_nt), jnp.asarray(self._eos),
             jnp.asarray(self._min_len), jnp.asarray(self._rep_pen),
             self._presence_arg(), jnp.asarray(self._rseed, jnp.int32)),
            rows=int((adv > 0).sum()),
            budget_used=used,
            budget_wasted=computed - used,
            drafts=int(dlen.sum()))
        self._keep_caches(res[0])
        self._budget_steps += 1
        self._budget_tokens_used += used
        self._budget_prefill_tokens += int(pf_n.sum())
        self._budget_decode_tokens += len(dec_rows)
        self._budget_draft_tokens += int(dlen.sum())
        self._budget_padding_tokens += computed - used
        if not k:
            return self._harvest_budget_plain(res, ev, pf_n, tail)
        out = np.asarray(res[1])
        if full_logits:
            out = out.astype(np.float32)
        chain_out = {s: out[s:s + 1] for s in regd}
        for e, st in zip(segs, starts):
            chain_out[e[0]] = out[b + st: b + st + len(e[1])]
        return self._harvest_budget_chain(chain_out, ev, pf_n, dec_rows,
                                          drafts, dlen, full_logits)

    def _decode_one_chunk(self):
        chunk = self.decode_chunk
        stk = self.dec._stacked()
        e_arrays = [p._data for p in self.dec._embed_params]
        h_arrays = self.dec._maybe_quant_head(
            [p._data for p in self.dec._head_params])
        if self.paged:
            # cover this chunk's write window before dispatch (lazy
            # mapping as lens grows + the COW guard for forked slots)
            for s in range(self.num_slots):
                if self._active[s]:
                    self._ensure_writable(
                        s, int(self._lens[s]),
                        min(int(self._lens[s]) + chunk,
                            self._budget_pos(s)))
        res, ev = self._run_dispatch(
            ("decode", chunk), self._build_decode_chunk, (3,),
            (stk, e_arrays, h_arrays, self._cache_arg(),
             jnp.asarray(self._tok), jnp.asarray(self._lens),
             jnp.asarray(self._active), jnp.asarray(self._nt),
             jnp.asarray(self._max_nt), jnp.asarray(self._eos),
             jnp.asarray(self._min_len), jnp.asarray(self._rep_pen),
             self._presence_arg(), jnp.asarray(self._rseed, jnp.int32)),
            rows=int(self._active.sum()))
        (out, tok, lens, active, nt, presence, (toks, emitted)) = res
        self._keep_caches(out)
        if self._rep_on:
            self._presence = presence
        toks = np.asarray(toks)                  # [chunk, B]
        emitted = np.asarray(emitted)            # [chunk, B] bool
        # np.array (not asarray): host slot state stays WRITABLE — jax
        # outputs view as read-only numpy
        self._tok = np.array(tok)
        self._lens = np.array(lens)
        self._nt = np.array(nt)
        still_active = np.array(active)

        n_emitted = 0
        now = self.clock()
        for s in range(self.num_slots):
            req = self._slot_req[s]
            if req is None or not self._active[s]:
                continue
            hits = emitted[:, s]
            req.tokens.extend(int(t) for t in toks[hits, s])
            self._class_tokens[req.priority] += int(hits.sum())
            if hits.any():
                self.telemetry.req_event(req.rid, "decode", now)
            if self._drafters is not None:
                # spec engines reach here through the thin-draft
                # fallback: the drafter context must track every
                # emitted token or later proposals go stale
                self._drafters[s].update(toks[hits, s])
            n_emitted += int(hits.sum())
            if not still_active[s]:
                self._finish(req, now)
        self._active = still_active
        self._decode_steps += n_emitted      # 1 row-step per token here
        self.telemetry.finish_step(
            ev, self.clock() if ev is not None else 0.0,
            tokens=n_emitted)
        return n_emitted

    def _spec_decode_step(self):
        """One speculative decode iteration over ALL slots: per-slot
        n-gram draft proposals ride into ONE compiled K+1-position
        verify step as pure data, and acceptance/rollback happen here
        on the returned logits — greedy exact-match (token-identical to
        the normal decode path) or rejection sampling with the
        bonus-token resample. A slot's cache_lens advances by
        accepted+1 only; rejected positions' K/V were write-masked or
        are overwritten before ever becoming attendable
        (write-then-attend at the advanced lens). Slots without a
        usable draft ship dlen == 0 and degrade to a normal one-token
        step inside the SAME executable — zero retraces across churn,
        counted by the usual trace spy."""
        from .spec_decode import (filtered_probs, greedy_accept,
                                  propose_claims, rejection_sample,
                                  truncate_emitted)
        k = self.spec_k
        b = self.num_slots
        stk = self.dec._stacked()
        e_arrays = [p._data for p in self.dec._embed_params]
        h_arrays = self.dec._maybe_quant_head(
            [p._data for p in self.dec._head_params])
        drafts, dlen = propose_claims(
            self._drafters, [s for s in range(b) if self._active[s]],
            k, self._max_nt - self._nt)
        if int(dlen.sum()) < self._spec_min_draft * self._active.sum():
            # thin-draft phase (cold contexts, non-repetitive spans):
            # the plain decode chunk emits decode_chunk tokens/row per
            # dispatch — cheaper than a near-empty verify step. Both
            # executables are warm, so the switch is pure scheduling.
            return self._decode_one_chunk()
        toks = np.zeros((b, k + 1), np.int32)
        toks[:, 0] = self._tok
        toks[:, 1:] = drafts
        if self.paged:
            # cover the verify block's write window [lens, lens+K]
            # before dispatch — accepted positions become attendable
            # next step, so every VALID draft write must land (an
            # unmapped entry would silently drop it)
            for s in range(self.num_slots):
                if self._active[s]:
                    self._ensure_writable(
                        s, int(self._lens[s]),
                        min(int(self._lens[s]) + k + 1,
                            self._budget_pos(s)))
        (caches_out, out), ev = self._run_dispatch(
            ("verify", k),
            lambda: self.dec._build_verify_core(
                k, self._rep_on, greedy_out=not self.do_sample),
            (3,),
            (stk, e_arrays, h_arrays, self._cache_arg(),
             jnp.asarray(toks), jnp.asarray(self._lens),
             jnp.asarray(dlen), jnp.asarray(self._active),
             jnp.asarray(self._nt), jnp.asarray(self._eos),
             jnp.asarray(self._min_len), jnp.asarray(self._rep_pen),
             self._presence_arg()),
            rows=int(self._active.sum()), drafts=int(dlen.sum()))
        self._keep_caches(caches_out)
        if self.do_sample:
            logits = np.asarray(out).astype(np.float32)  # [B, K+1, V]
            self._get_spec_rng()
        else:
            # greedy: the step returns just the [B, K+1] argmax chain —
            # the only thing exact-match acceptance reads
            argmax = np.asarray(out)
        n_emitted = 0
        now = self.clock()
        new_rows, new_cols = [], []
        for s in range(self.num_slots):
            req = self._slot_req[s]
            if req is None or not self._active[s]:
                continue
            m = int(dlen[s])
            if self.do_sample:
                probs = filtered_probs(logits[s, :m + 1], self.top_k,
                                       self.top_p, self.temperature)
                kept, _ = rejection_sample(drafts[s, :m], probs,
                                           self._spec_rng)
            else:
                kept, _ = greedy_accept(drafts[s, :m],
                                        argmax[s, :m + 1])
            eos = None if self._eos[s] < 0 else int(self._eos[s])
            emitted, hit_eos = truncate_emitted(
                kept, int(self._max_nt[s] - self._nt[s]), eos)
            self._nt[s] += len(emitted)
            req.tokens.extend(emitted)
            self._class_tokens[req.priority] += len(emitted)
            n_emitted += len(emitted)
            self._lens[s] += len(emitted)
            self._tok[s] = emitted[-1]
            # per-row accounting: 1 verify row-step emitted
            # len(emitted) tokens, len(emitted)-1 of them drafts —
            # tokens == steps + accepted reconciles by construction
            self._decode_steps += 1
            self._draft_proposed += m
            self._draft_accepted += len(emitted) - 1
            self.telemetry.req_event(req.rid, "verify", now)
            self._drafters[s].update(emitted)
            if self._rep_on:
                new_rows.extend([s] * len(emitted))
                new_cols.extend(emitted)
            if hit_eos or self._nt[s] >= self._max_nt[s]:
                self._active[s] = False
                self._finish(req, now)
        if self._rep_on and new_rows:
            # rollback is structural: the verify step's speculative
            # presence carry was DISCARDED — only accepted tokens join
            self._presence = self._presence.at[
                jnp.asarray(new_rows), jnp.asarray(new_cols)].set(True)
        self.telemetry.finish_step(
            ev, self.clock() if ev is not None else 0.0,
            tokens=n_emitted)
        return n_emitted

    def _expire_deadlines(self, now):
        """Evict every request past its deadline_s — queued requests are
        shed before they ever cost a prefill; RUNNING ones release their
        slot through the normal eviction machinery (_finish resets the
        slot bookkeeping; the cache row needs no zeroing)."""
        for q in self._queues.values():
            for req in [r for r in q
                        if r.deadline_s is not None
                        and now - r.t_submit > r.deadline_s]:
                q.remove(req)
                self._finish(req, now, expired=True)
        for s in range(self.num_slots):
            req = self._slot_req[s]
            if (req is not None and req.deadline_s is not None
                    and now - req.t_submit > req.deadline_s):
                self._finish(req, now, expired=True)
        # parked requests age too: the deadline clock never pauses in
        # the parking lot (park time is queue-attributed delay) — an
        # expired one is shed HERE, releasing its kv commitment exactly
        # once through the normal _finish path (slot is already None)
        for rid in [r for r, st in self._parked.items()
                    if st["deadline_s"] is not None
                    and now - self._req_index[r].t_submit
                    > st["deadline_s"]]:
            self._finish(self._req_index[rid], now, expired=True)

    def _finish(self, req, now, expired=False):
        req.state = "expired" if expired else "finished"
        req.t_done = now
        if expired:
            self._expired += 1
        else:
            self._finished += 1
            # queue-time vs service-time decomposition + SLO verdict:
            # queue = submit -> admitted (0 for forked clones), service
            # = admitted -> finished; the mean inter-token gap stands
            # in for the per-request ITL objective (tokens harvest in
            # batches — there are no per-token timestamps to p99 over)
            t_adm = req.t_admit if req.t_admit is not None else now
            queue_s = max(t_adm - req.t_submit, 0.0)
            service_s = max(now - t_adm, 0.0)
            n = len(req.tokens)
            itl_s = (max(req.t_done - req.t_first, 0.0) / (n - 1)
                     if n > 1 and req.t_first is not None else 0.0)
            verdict = self._slo.classify(queue_s, service_s, req.ttft_s,
                                         itl_s, req.latency_s)
            if verdict == "ok":
                self._slo_ok += 1
            elif verdict == "queue":
                self._slo_violated_queue += 1
                # per-class queue-violation attribution: the autoscaler
                # reads the HIGH-class series (scale on premium pain
                # only) and the gateway's shed logic reads the split
                self._slo_vq_class[req.priority] += 1
            else:
                self._slo_violated_service += 1
            # histogram observation happens HERE, not at the first
            # token: expired requests must stay out of the percentiles
            # (their "latency" is an eviction time), same contract the
            # old done-list scan enforced
            self.telemetry.observe_request(req.ttft_s, req.latency_s,
                                           queue_s, service_s)
        self.telemetry.req_done(req.rid, req.state, now)
        self.results[req.rid] = req.result()
        # bounded results (the telemetry ring size): a long-lived engine
        # must not leak one dict per finished request — totals live in
        # the window counters + the Prometheus lifetime base, recent
        # results stay retrievable
        while len(self.results) > self._results_cap:
            self.results.pop(next(iter(self.results)))
        # a tracked request's record outlives the cap until its reader
        # drains it (harvest_new_tokens done=True / release); untracked
        # requests drop from the index now — results keeps the bounded
        # record, exactly the old lifecycle
        if req.rid not in self._harvest:
            self._req_index.pop(req.rid, None)
        # a parked request finishing (deadline expiry) drops its host
        # copy; its blocks/reservation were already released at preempt
        self._parked.pop(req.rid, None)
        if self.paged:
            self._kv_committed -= self._blocks_needed(req.prompt.size,
                                                      req.max_new_tokens)
        s = req.slot
        if s is None:                # shed from the queue, never admitted
            return
        self._slot_req[s] = None
        self._active[s] = False
        self._pf_left[s] = 0             # a mid-prefill eviction stops
        if self.paged:
            # eviction frees the slot's block REFERENCES: blocks the
            # prefix store (or a fork twin) still holds stay resident,
            # everything else returns to the pool free list. The table
            # row resets to the sentinel, so the unmasked idle-row
            # rewrite at the frozen lens drops instead of landing.
            self._kv_reserved -= self._blocks_needed(req.prompt.size,
                                                     req.max_new_tokens)
            self._free_slot_blocks(s)
        # slot eviction IS this bookkeeping: the cache row is left as-is
        # (positions >= cache_lens are never attendable; the next
        # admission's masked prefill overwrites [0, plen) in place)

    # ------------------------------------------------------------ helpers
    def _prefill_chunks(self, maxp):
        """Prefill dispatch sizes for a prompt of length maxp: full
        `prefill_cap` chunks, then ONE chunk rounded UP to the next
        power of two (bounded variant set, like the decode ladder — but
        up, not down). One admission is one prefill dispatch for any
        prompt <= cap; the tail steps are write-masked no-ops. Serving
        is dispatch-bound at admission time: a 3-dispatch 4+2+1 ladder
        walk per admitted request measurably beat the masked tail's
        wasted compute on the serving bench."""
        out, pos = [], 0
        while pos < maxp:
            rem = maxp - pos
            c = (self.prefill_cap if rem >= self.prefill_cap
                 else 1 << (rem - 1).bit_length())
            out.append(c)
            pos += c
        return out

    def _presence_init(self):
        if self._presence is None:
            vocab = int(self.dec._head_params[0].shape[1])
            self._presence = jnp.zeros((self.num_slots, vocab), bool)
        return self._presence

    def _presence_arg(self):
        if not self._rep_on:
            # a [B, 1] placeholder keeps the compiled signature stable
            return jnp.zeros((self.num_slots, 1), bool)
        return self._presence_init()


def _kv_payload_bytes(blocks):
    """Wire size of a KV handoff payload: the kv tensors plus int8
    scales when present — what a cross-host transport would move."""
    total = 0
    for blk in blocks:
        total += int(blk["kv"].nbytes)
        if "sc" in blk:
            total += int(blk["sc"].nbytes)
    return total


def _penalize_slots(logits, presence, rep_pen, nt, min_len, eos_ids):
    """Vectorized-over-slots logit controls (reference: generation's
    logit processors, here with PER-SLOT parameters as data):
    repetition_penalty divides positive / multiplies negative logits of
    context tokens, per row (rows at 1.0 are exact no-ops); min_length
    suppresses each row's OWN eos column while that row has generated
    fewer than its min_length tokens. eos_ids < 0 means no eos."""
    if presence is not None:
        pen = rep_pen[:, None]
        logits = jnp.where(
            presence,
            jnp.where(logits > 0, logits / pen, logits * pen),
            logits)
    cols = jnp.arange(logits.shape[1])[None, :]
    is_eos = cols == eos_ids[:, None]
    suppress = is_eos & (nt < min_len)[:, None]
    return jnp.where(suppress, -1e30, logits)
