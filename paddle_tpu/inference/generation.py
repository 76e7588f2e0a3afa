"""Autoregressive generation loops.

Capability parity: the decode driver around
fused_multi_transformer_op.cu (paddle/fluid/operators/fused/) and
PaddleNLP-style `generate()` (greedy / sampling / top-k / top-p).

Two paths:
  * generate(model, ...)        — model-agnostic: re-runs the forward on the
    growing prefix each step (correct for any causal LM; XLA caches one
    executable per prefix-length bucket).
  * generate_fused(fmt, ...)    — FusedMultiTransformer decode: static-shape
    KV ring cache + the Pallas flash-decode kernel
    (paddle_tpu/ops/pallas/decode_attention.py), one compiled step reused
    for every position — the reference's fused decode loop, TPU-style.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..core.rng import next_key
from ..tensor.tensor import Tensor, no_grad

__all__ = ["generate", "generate_fused", "FusedDecoder",
           "dispatch_kind", "DISPATCH_KINDS", "STACKED_PARAM_SPECS"]

# ---- dispatch-kind vocabulary (serving telemetry) ---------------------
# Every compiled executable the serving stack can dispatch is built
# here (or keyed to a core built here), and the telemetry step timeline
# labels each dispatch with ONE canonical kind. Keeping the vocabulary
# next to the core builders means a new executable kind cannot reach
# the engine without naming itself for the timeline.
DISPATCH_KINDS = {
    "bulk_admit": "prefill",      # one-row causal-flash prompt pass
    "prefill": "prefill",         # masked chunked prefill scan
    "admit_sample": "admit",      # first-token sample on prefill hiddens
    "decode": "decode",           # the decode-chunk scan
    "verify": "verify",           # the K+1-position spec-verify block
    "budget": "budget",           # the [B, C] token-budget core
    "flat_budget": "budget",      # the token-flattened [T] budget core
}


def dispatch_kind(jit_key):
    """Canonical telemetry kind for a serving jit-cache key (keys are
    tuples whose head names the executable family; shape parameters
    follow). Unknown families pass through as their own name so a new
    dispatch is visible — just unclassified — rather than dropped."""
    return DISPATCH_KINDS.get(jit_key[0], str(jit_key[0]))


# ---- stacked-weight sharding table (tensor parallel over 'mp') --------
# Every key _stacked() can emit MUST have an explicit entry here —
# sharded on 'mp' or declared-replicated with P() — enforced twice:
# placement raises on an unknown key, and tools/check_sharding_spec.py
# (tier-1) rebuilds both weight flavors and diffs the keys against this
# table, so a new param key cannot silently replicate.
#
# Layout (Megatron-style; the KV pool/rings shard by head on the same
# 'mp' axis, see init_paged_cache / shard_caches):
#   * qkv_w is pre-fused HEAD-MAJOR at stack time — [L, nh*3*hd, E]
#     with nh outermost in the fused axis — so sharding the fused axis
#     IS the head shard and the in-trace (B,S,F)->(B,S,nh,3,hd) unfuse
#     stays GSPMD-representable (the raw (3,nh,..) layout sharded on
#     nh would gather the full weight at every dispatch).
#   * column-parallel (output-axis) shards: qkv_w/qkv_b, f1_w/f1_b —
#     no cross-device reduction, each device computes its own heads /
#     FFN columns exactly.
#   * row-parallel (contracting-axis) shards: lin_w, f2_w — GSPMD
#     psums the partial products inside the step core; their biases
#     and per-OUT-channel int8 scales (lin_w_s/f2_w_s) apply to the
#     summed [*, E] result, hence declared-replicated.
#   * qkv_w_s / f1_w_s scale a column-parallel output axis: they shard
#     WITH their weight (a replicated mirror would gather the sharded
#     dot result to apply it — the int8 flavor's silent-gather trap).
#   * LN params are tiny and feed every shard: replicated.
# PartitionSpec pads missing trailing dims with None, so one entry per
# key covers both the fp and int8 array ranks.
STACKED_PARAM_SPECS = {
    "ln_s": PartitionSpec(), "ln_b": PartitionSpec(),
    "fln_s": PartitionSpec(), "fln_b": PartitionSpec(),
    "qkv_w": PartitionSpec(None, "mp"),    # [L, nh*3*hd, E] fused col
    "qkv_b": PartitionSpec(None, "mp"),    # [L, nh*3*hd]
    "qkv_w_s": PartitionSpec(None, None, "mp"),  # [L, 1, nh*3*hd]
    "lin_w": PartitionSpec(None, "mp"),    # [L, nh*hd, E] row shard
    "lin_b": PartitionSpec(),              # applies post-psum
    "lin_w_s": PartitionSpec(),            # per-out-channel of the psum
    "f1_w": PartitionSpec(None, None, "mp"),     # [L, E, FF] col
    "f1_b": PartitionSpec(None, "mp"),     # [L, FF]
    "f1_w_s": PartitionSpec(None, None, "mp"),   # [L, 1, FF]
    "f2_w": PartitionSpec(None, "mp"),     # [L, FF, E] row shard
    "f2_b": PartitionSpec(),
    "f2_w_s": PartitionSpec(),
}


def _absmax_int8(w, axis):
    """Per-slice absmax int8 quantization (ONE recipe for every absmax
    site: weight-only layer stacks + LM head, and the int8 KV-cache
    writes in prefill / decode / serving bulk-admit — the i8 write
    kernel documents its in-kernel quant as bit-identical to this):
    scales = absmax/127 over the reduced axis with a zero-slice guard;
    values clip/round to int8. Returns (int8 array, fp32 scales with
    the reduced axis kept)."""
    a = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(a / jnp.maximum(s, 1e-8)),
                 -127, 127).astype(jnp.int8)
    return q, s


def _absmax_int4(w, axis):
    """int4 flavor of _absmax_int8 — SAME recipe, 4-bit range: scales =
    absmax/7 over the reduced axis (zero-slice guarded), values
    clip/round to [-7, 7] held in int8 nibbles pending _pack_int4.
    Returns (int8 array of int4-valued entries, fp32 scales with the
    reduced axis kept)."""
    a = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 7.0
    q = jnp.clip(jnp.round(a / jnp.maximum(s, 1e-8)),
                 -7, 7).astype(jnp.int8)
    return q, s


def _pack_int4(q, axis):
    """Pack adjacent pairs of int4-valued int8 entries along ``axis``
    into single bytes: the LOW nibble holds the even index, the HIGH
    nibble the odd one (both sign-extended on unpack via arithmetic
    shifts — see ops.pallas.fused_dequant_matmul). The axis must be
    even-length; halving it is what halves the int8 flavor's bytes."""
    axis = axis % q.ndim
    if q.shape[axis] % 2:
        raise ValueError(
            f"_pack_int4: axis {axis} has odd length {q.shape[axis]} — "
            "int4 packing pairs adjacent contracted elements")
    lo = jax.lax.slice_in_dim(q, 0, None, 2, axis)
    hi = jax.lax.slice_in_dim(q, 1, None, 2, axis)
    return ((lo & jnp.int8(0x0F))
            | jnp.left_shift(hi, 4).astype(jnp.int8)).astype(jnp.int8)


def _filter_logits(logits, do_sample, top_k, top_p, temperature):
    if not do_sample:
        return logits
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        kth = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < kth, -1e30, logits)
    return logits


def _penalize(logits, presence, repetition_penalty, nt, min_length, eos):
    """Reference generate() logit controls (PaddleNLP GenerationMixin):
    repetition_penalty divides positive / multiplies negative logits of
    every token already in the context (prompt + generated), and
    min_length suppresses eos until `nt` generated tokens exist. Pure
    jnp — usable inside compiled decode steps."""
    if repetition_penalty != 1.0 and presence is not None:
        logits = jnp.where(
            presence,
            jnp.where(logits > 0, logits / repetition_penalty,
                      logits * repetition_penalty),
            logits)
    if min_length and eos is not None:
        logits = logits.at[:, eos].set(
            jnp.where(nt < min_length, -1e30, logits[:, eos]))
    return logits


def _host_seed(key):
    """Fold a jax PRNG key (typed or raw uint32) into a numpy
    RandomState seed — the host-side acceptance sampler of speculative
    decoding draws from numpy, seeded off the same stream the device
    samplers advance."""
    data = np.asarray(jax.random.key_data(key)).ravel()
    return int(data[-1]) & 0x7FFFFFFF


def _presence_from(ids, vocab):
    p = jnp.zeros((ids.shape[0], vocab), bool)
    rows = jnp.arange(ids.shape[0])[:, None]
    return p.at[rows, ids].set(True)


def _sample_next(logits, do_sample, top_k, top_p, temperature, key=None):
    """logits: [B, V] jnp array -> [B] int32 token ids."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _filter_logits(logits, do_sample, top_k, top_p, temperature)
    return jax.random.categorical(key if key is not None else next_key(),
                                  logits, axis=-1).astype(jnp.int32)


def _sample_rows(logits, do_sample, top_k, top_p, temperature, seeds, nt):
    """Scheduling-invariant per-row sampling for the serving engine:
    row b draws from fold_in(PRNGKey(seeds[b]), nt[b]) — the randomness
    behind a request's nt-th generated token depends ONLY on (request
    seed, position), never on which dispatch produced it. That makes
    sampled outputs identical across schedulers (phase-prefill vs the
    token-budget step, any chunk boundary, any slot assignment), which
    is what lets the chunked-vs-phase parity tests assert EXACT sampled
    token equality. Stateless by construction: a discarded sample (a
    masked row, a teacher-forced prefill position) consumes nothing.
    logits: [B, V]; seeds, nt: [B] int32 -> [B] int32 token ids."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _filter_logits(logits, do_sample, top_k, top_p, temperature)

    def one(seed, n, lg):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), n)
        return jax.random.categorical(key, lg)
    return jax.vmap(one)(seeds, nt, logits).astype(jnp.int32)


def _make_budget_tail(hidden, head_logits, penalize_slots, rep_on,
                      do_sample, top_k, top_p, temperature, nscan):
    """The budget cores' TRAILING decode scan (the decode-chunk body
    verbatim): after the block samples, rows that are decoding keep
    emitting `nscan` tokens in the SAME dispatch so mixed steps never
    slow decode below the plain chunk. ONE owner shared by the
    row-aligned [B, C] core and the flat [T] core — the two layouts'
    tail iterations cannot drift numerically."""
    def run(stk, e_arrays, h_arrays, tok, caches, lens, active, nt,
            presence, max_nt, eos_ids, min_len, rep_pen, seeds):
        def body(carry, _):
            tok, caches, lens, active, nt, presence = carry
            xs, caches = hidden(stk, e_arrays, caches, tok, lens)
            lg = head_logits(h_arrays, xs)
            lg = lg.reshape(lg.shape[0], -1)
            lg = penalize_slots(
                lg, presence if rep_on else None, rep_pen, nt,
                min_len, eos_ids)
            nxt = _sample_rows(lg, do_sample, top_k, top_p,
                               temperature, seeds, nt)
            emitted = active
            h_eos = (eos_ids >= 0) & (nxt == eos_ids)
            step_ = active.astype(jnp.int32)
            nt2 = nt + step_
            lens2 = lens + step_
            act2 = active & ~h_eos & (nt2 < max_nt)
            tok2 = jnp.where(emitted, nxt, tok)
            if rep_on:
                presence = presence.at[
                    jnp.arange(nxt.shape[0]), nxt].max(emitted)
            return (tok2, caches, lens2, act2, nt2,
                    presence), (nxt, emitted)
        return jax.lax.scan(
            body, (tok, caches, lens, active, nt, presence), None,
            length=nscan)
    return run


@no_grad()
def generate(model, input_ids, max_new_tokens: int = 20,
             eos_token_id: Optional[int] = None, do_sample: bool = False,
             top_k: int = 0, top_p: float = 1.0, temperature: float = 1.0,
             num_beams: int = 1, length_penalty: float = 1.0,
             min_length: int = 0, repetition_penalty: float = 1.0,
             no_repeat_ngram_size: int = 0):
    """Causal-LM generation; input_ids [B, S] Tensor/ndarray -> [B, S+T].

    Greedy by default; sampling with top-k/top-p/temperature when
    do_sample=True; beam search when num_beams > 1 (reference:
    generation's beam_search decode strategy / fluid beam_search op —
    length-penalized GNMT scoring, finished beams frozen on eos). Stops
    early only when every sequence (or every beam) emitted eos.
    """
    model.eval()
    ids = input_ids._data if isinstance(input_ids, Tensor) else \
        jnp.asarray(np.asarray(input_ids))
    if num_beams > 1:
        if do_sample:
            raise ValueError("beam search (num_beams>1) is deterministic; "
                             "do_sample=True is not supported with it")
        if min_length or repetition_penalty != 1.0:
            raise NotImplementedError(
                "min_length/repetition_penalty with beam search is not "
                "supported; use greedy/sampling generation")
        return _beam_search(model, ids, max_new_tokens, eos_token_id,
                            num_beams, length_penalty)
    finished = jnp.zeros((ids.shape[0],), bool)
    presence = None
    eos_i = None if eos_token_id is None else int(eos_token_id)
    rep_on = repetition_penalty != 1.0
    for nt in range(max_new_tokens):
        logits = model(Tensor(ids))
        logits = (logits._data if isinstance(logits, Tensor)
                  else logits)[:, -1]
        if min_length or rep_on:
            if rep_on and presence is None:
                presence = _presence_from(ids, logits.shape[-1])
            logits = _penalize(logits, presence, repetition_penalty,
                               nt, min_length, eos_i)
        if no_repeat_ngram_size:
            # reference no_repeat_ngram logits processor: ban every token
            # that would complete an already-seen n-gram. Host-side (this
            # path re-runs the forward per step anyway); the fused decoder
            # documents it as unsupported.
            n = int(no_repeat_ngram_size)
            ids_np = np.asarray(ids)
            if ids_np.shape[1] >= n - 1:
                banned = np.zeros(logits.shape, bool)
                for b_ in range(ids_np.shape[0]):
                    row = ids_np[b_].tolist()
                    tail = tuple(row[len(row) - (n - 1):]) if n > 1 else ()
                    for s_ in range(len(row) - n + 1):
                        if tuple(row[s_:s_ + n - 1]) == tail:
                            banned[b_, row[s_ + n - 1]] = True
                logits = jnp.where(jnp.asarray(banned), -1e30, logits)
        nxt = _sample_next(logits, do_sample, top_k, top_p,
                           temperature)
        if eos_token_id is not None:
            nxt = jnp.where(finished, eos_token_id, nxt)
            finished = finished | (nxt == eos_token_id)
        if presence is not None:
            presence = presence.at[jnp.arange(nxt.shape[0]), nxt].set(True)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
        if eos_token_id is not None and bool(jnp.all(finished)):
            break
    return Tensor(ids)


def _beam_search(model, ids, max_new_tokens, eos_token_id, num_beams,
                 length_penalty):
    """Model-agnostic beam search: re-runs the forward on the growing
    prefix (correct for any causal LM; XLA caches one executable per
    prefix length, shared across steps since all beams batch together).
    Finished beams are frozen: they may only continue with eos at zero
    added score. Final selection is GNMT length-penalized."""
    b, s0 = ids.shape
    k = int(num_beams)
    eos = None if eos_token_id is None else int(eos_token_id)
    beams = jnp.repeat(ids[:, None], k, axis=1)          # [B, K, S]
    # only beam 0 is live at step one, else K identical top picks
    scores = jnp.full((b, k), -1e9, jnp.float32).at[:, 0].set(0.0)
    finished = jnp.zeros((b, k), bool)
    gen_len = jnp.zeros((b, k), jnp.int32)               # generated length
    # separate FINISHED pool (standard beam search): a completed
    # hypothesis must survive even if live continuations transiently
    # out-score it and evict it from the top-k — track the best
    # length-penalized finished sequence per batch row, eos-padded to the
    # current length each step
    best_fin_score = jnp.full((b,), -jnp.inf, jnp.float32)
    best_fin_seq = beams[:, 0]                           # [B, S] placeholder

    for _ in range(max_new_tokens):
        flat = beams.reshape(b * k, beams.shape[-1])
        logits = model(Tensor(flat))
        logits = (logits._data if isinstance(logits, Tensor)
                  else logits)[:, -1]
        v = logits.shape[-1]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        logp = logp.reshape(b, k, v)
        if eos is not None:
            only_eos = jnp.where(jnp.arange(v)[None, None, :] == eos,
                                 0.0, -jnp.inf)
            logp = jnp.where(finished[..., None], only_eos, logp)
        cand = scores[..., None] + logp                  # [B, K, V]
        top_scores, top_idx = jax.lax.top_k(cand.reshape(b, k * v), k)
        beam_idx = top_idx // v                          # [B, K]
        tok = (top_idx % v).astype(beams.dtype)
        beams = jnp.take_along_axis(beams, beam_idx[..., None], axis=1)
        beams = jnp.concatenate([beams, tok[..., None]], axis=-1)
        finished = jnp.take_along_axis(finished, beam_idx, axis=1)
        gen_len = jnp.take_along_axis(gen_len, beam_idx, axis=1)
        gen_len = jnp.where(finished, gen_len, gen_len + 1)
        scores = top_scores
        if eos is not None:
            newly = ~finished & (tok == eos)
            finished = finished | newly
            # admit newly finished hypotheses into the finished pool
            pen = jnp.maximum(gen_len, 1).astype(jnp.float32) \
                ** length_penalty
            cand_fin = jnp.where(newly, scores / pen, -jnp.inf)
            row_best = jnp.argmax(cand_fin, axis=1)              # [B]
            row_score = jnp.take_along_axis(
                cand_fin, row_best[:, None], axis=1)[:, 0]
            better = row_score > best_fin_score
            best_fin_seq = jnp.concatenate(                       # pad
                [best_fin_seq,
                 jnp.full((b, 1), eos, beams.dtype)], axis=-1)
            chosen = jnp.take_along_axis(
                beams, row_best[:, None, None], axis=1)[:, 0]
            best_fin_seq = jnp.where(better[:, None], chosen,
                                     best_fin_seq)
            best_fin_score = jnp.maximum(best_fin_score, row_score)
            if bool(jnp.all(finished)):
                break

    lp = jnp.maximum(gen_len, 1).astype(jnp.float32) ** length_penalty
    norm = scores / lp
    best = jnp.argmax(norm, axis=1)                      # [B]
    live_score = jnp.take_along_axis(norm, best[:, None], axis=1)[:, 0]
    out = jnp.take_along_axis(
        beams, best[:, None, None], axis=1)[:, 0]
    if eos is not None:
        # pad the finished pool to the final length and take the winner
        pad = out.shape[-1] - best_fin_seq.shape[-1]
        if pad > 0:
            best_fin_seq = jnp.concatenate(
                [best_fin_seq, jnp.full((b, pad), eos, beams.dtype)],
                axis=-1)
        use_fin = best_fin_score > live_score
        out = jnp.where(use_fin[:, None], best_fin_seq, out)
    return Tensor(out)


class FusedDecoder:
    """Compiled multi-layer KV-cache decode around FusedMultiTransformer.

    Parity: the decode driver of fused_multi_transformer_op.cu ::
    FusedMultiTransformerOp — all decoder layers batched into ONE compiled
    step per token. TPU-native realization:
      * the KV cache is a layer-stacked static ring buffer
        [L, 2, B, H, Smax, D] in kernel layout (no per-step transposes or
        reallocation; position is data, so one executable serves every t);
      * the cache is IN-PLACE: it rides the layer scan as carry with one
        tiny dynamic_update_slice per layer (the reference's in-place
        per-step cache write in fused_multi_transformer_op.cu), and the
        Pallas flash-decode kernel reads layer l's blocks straight out of
        the stacked buffer via a scalar-prefetch layer index
        (decode_attention_stacked) — the full stack is never copied per
        token;
      * the layer loop is a lax.scan over stacked layer params — the
        kernel compiles once and streams KV blocks for each layer;
      * under an active mesh with mp >= 2 the stacked kernel runs
        TP-sharded via shard_map over 'mp' (reference: mp-sharded heads
        in fused_multi_transformer_op.cu): heads are the sharded dim, so
        each device streams its local head blocks through the SAME
        kernel with no collectives; caches are annotated
        P(None,None,None,'mp',None,None). The int8 cache composes (stack
        and scales both shard on the head axis). Shapes the kernel can't
        tile fall back to a dense masked form GSPMD shards over 'mp'.

    embed / head are the model's surrounding Layers (token embedding and
    LM head); their params are passed as jit arguments, not baked in.
    """

    def __init__(self, fmt, embed, head, max_seq_len, use_rotary=False,
                 rope_base=10000.0, weight_quant=None, kv_quant=None):
        from ..nn.layer.layers import Layer
        # first-class quant config: an explicit ctor arg WINS over the
        # env knobs (PADDLE_TPU_DECODE_INT4_WEIGHTS /
        # PADDLE_TPU_DECODE_INT8_WEIGHTS / PADDLE_TPU_DECODE_INT8_CACHE
        # stay as deploy-time fallbacks); None defers to the env.
        # Explicit config fails FAST — an unknown mode or an int4 model
        # whose contracted axes cannot pack is a ValueError here, not a
        # first-dispatch surprise.
        if weight_quant not in (None, "none", "int8", "int4"):
            raise ValueError(
                f"weight_quant={weight_quant!r}: expected 'none', "
                "'int8' or 'int4'")
        if kv_quant not in (None, "none", "int8"):
            raise ValueError(
                f"kv_quant={kv_quant!r}: expected 'none' or 'int8' — "
                "the KV pool has no int4 flavor (per-row absmax at 4 "
                "bits clips decode tails; weights are where int4 pays)")
        self._weight_quant_arg = weight_quant
        self._kv_quant_arg = kv_quant
        self.fmt = fmt
        self.embed = embed
        self.head = head
        # ring capacity rounds up to a 128-multiple: the stacked-cache
        # Pallas kernel tiles Smax exactly (padding the stacked buffer
        # per call would copy every layer), and extra capacity only means
        # a slightly longer ring — callers still get >= max_seq_len
        self.smax = -(-int(max_seq_len) // 128) * 128
        self.use_rotary = use_rotary
        if use_rotary and float(rope_base) != 10000.0:
            raise NotImplementedError(
                "FusedDecoder prefill uses the fused stack's default rotary "
                "base (10000); plumb rotary_emb_base through "
                "fused_multi_transformer before changing it")
        self.rope_base = rope_base
        self._embed_params = list(embed.parameters()) if isinstance(
            embed, Layer) else []
        self._head_params = list(head.parameters()) if isinstance(
            head, Layer) else []
        self._scan_cache = {}      # (sample cfg, mesh, chunk, eos) -> jitted scan
        self._stk_cache = None
        self._paths = set()        # see step_paths()
        if self._weight_quant_mode() == "int4":
            self._validate_int4_dims()

    def step_paths(self) -> str:
        """Which implementation the compiled step cores TOOK at each gate
        that can fall through to an XLA path, e.g.
        ``"attn=paged_pallas"`` or ``"attn=paged_xla_gather,mm=int4_
        nibble_xla"`` — noted while a core traces (once per compiled
        core; python side effects run at trace time only), so it reports
        the program that runs, not what env vars and shapes suggest.
        Empty until the first core has traced."""
        return ",".join(sorted(self._paths))

    # ------------------------------------------------------------ stacking
    def _weight_quant_mode(self) -> str:
        """The serving weight flavor: 'none' | 'int8' | 'int4'. An
        explicit ctor weight_quant wins; otherwise the env knobs decide
        (INT4 outranks INT8 when both are set — the more aggressive
        opt-in is the intended one)."""
        if self._weight_quant_arg is not None:
            return ("none" if self._weight_quant_arg == "none"
                    else self._weight_quant_arg)
        if os.environ.get("PADDLE_TPU_DECODE_INT4_WEIGHTS") == "1":
            return "int4"
        if os.environ.get("PADDLE_TPU_DECODE_INT8_WEIGHTS") == "1":
            return "int8"
        return "none"

    def _validate_int4_dims(self):
        """int4 packs TWO adjacent contracted-axis elements per byte, so
        every contracted axis of the stacked weights must be even:
        embed_dim (qkv_w / f1_w contract E), num_heads*head_dim (lin_w
        contracts the concatenated head axis) and ffn_dim (f2_w).
        Raises up front — the packed stack cannot be built otherwise."""
        f = self.fmt
        e = int(f.qkv_weights[0]._data.shape[-1])
        ff = int(f.ffn1_weights[0]._data.shape[-1])
        heads = f.num_heads * f.head_dim
        bad = [n for n, v in (("embed_dim", e),
                              ("num_heads*head_dim", heads),
                              ("ffn_dim", ff)) if v % 2]
        if bad:
            raise ValueError(
                "weight_quant='int4' needs even contracted axes to pack "
                f"two nibbles per byte; odd: {', '.join(bad)} "
                f"(embed_dim={e}, num_heads*head_dim={heads}, "
                f"ffn_dim={ff})")

    def _weight_shard_mesh(self):
        """The mesh the stacked weights (and a Linear LM head) shard
        over, or None (replicated — the pre-sharding behavior).
        Sharding is ON by default under an active mp mesh; opt out
        with PADDLE_SERVING_MESH_WEIGHTS=0. Falls back to None when
        the head / FFN axes do not divide mp — the engine surfaces
        that downgrade as a bring-up warning, and init_serving_mesh
        rejects it up front when given the model dims."""
        mesh = self._mesh_mp()
        if mesh is None or os.environ.get(
                "PADDLE_SERVING_MESH_WEIGHTS", "1") == "0":
            return None
        mp = dict(mesh.shape)["mp"]
        ff = int(self.fmt.ffn1_weights[0]._data.shape[-1])
        if self.fmt.num_heads % mp or ff % mp:
            return None
        if self._weight_quant_mode() == "int4":
            # the row-parallel stacks shard their PACKED contracted axis
            # (lin_w [L, nh*hd/2, E], f2_w [L, FF/2, E]): a byte-shard
            # boundary must land on a whole byte, so the HALF lengths
            # must divide mp too — else fall back to replicated weights
            # (init_serving_mesh rejects this up front when given dims)
            if (self.fmt.num_heads * self.fmt.head_dim // 2) % mp \
                    or (ff // 2) % mp:
                return None
        return mesh

    def _stacked(self):
        f = self.fmt
        # identity anchors are WEAK references: a dead weakref reads None
        # and never matches a live array, so the identity comparison is
        # sound (no recycled-id false match) without keeping the previous
        # parameter arrays alive — a strong hold meant a weight swap (new
        # checkpoint into the same decoder) pinned a full dead model copy
        # in HBM until the next restack completed (r4 verdict weak #7).
        import weakref
        version = [p._data for p in f.parameters()]
        # trace-time quant mode (ctor arg or env, see
        # _weight_quant_mode) and the weight-shard placement (mesh /
        # PADDLE_SERVING_MESH_WEIGHTS) are part of the cache identity:
        # flipping either must rebuild the stack, not reuse it — a
        # stack placed for the wrong mesh would silently reshard on
        # every dispatch
        mode = self._weight_quant_mode()
        env_sig = (mode, self._weight_shard_mesh())
        if self._stk_cache is not None and \
                self._stk_cache[2] == env_sig and \
                len(self._stk_cache[0]) == len(version) and \
                all(r() is b for r, b in zip(self._stk_cache[0], version)):
            return self._stk_cache[1]
        # drop stale stacked copies BEFORE building new ones so the two
        # stack generations never coexist in HBM
        self._stk_cache = None

        def stk(plist):
            return jnp.stack([p._data for p in plist])
        # qkv is pre-fused HEAD-MAJOR for BOTH weight flavors: the raw
        # per-layer [3, nh, hd, E] stacks become [L, nh*3*hd, E] (bias
        # [L, nh*3*hd]) with the head axis OUTERMOST in the fused dim.
        # Channel order is irrelevant to correctness (per-out-channel
        # dots and absmax scales commute with any output permutation —
        # qkv_of un-fuses with the matching (nh, 3, hd) reshape), but
        # it is what makes tensor parallel representable: sharding the
        # fused axis 'mp'-ways IS a head shard, and stays a head shard
        # through the in-trace unfuse reshape.
        qkv5 = stk(f.qkv_weights)              # [L, 3, nh, hd, E]
        qkvb4 = stk(f.qkv_biases)              # [L, 3, nh, hd]
        nl = qkv5.shape[0]
        out = {
            "ln_s": stk(f.ln_scales), "ln_b": stk(f.ln_biases),
            "qkv_w": jnp.swapaxes(qkv5, 1, 2).reshape(
                nl, -1, qkv5.shape[-1]),
            "qkv_b": jnp.swapaxes(qkvb4, 1, 2).reshape(nl, -1),
            "lin_w": stk(f.linear_weights), "lin_b": stk(f.linear_biases),
            "fln_s": stk(f.ffn_ln_scales), "fln_b": stk(f.ffn_ln_biases),
            "f1_w": stk(f.ffn1_weights), "f1_b": stk(f.ffn1_biases),
            "f2_w": stk(f.ffn2_weights), "f2_b": stk(f.ffn2_biases),
        }
        if mode == "int8":
            # weight-only int8 decode (reference: Predictor's weight-only
            # mode applied to the fused decode stack): at decode batch
            # sizes the step is WEIGHT-traffic bound (~2 bytes/param/token
            # in bf16 — ~250 MB/token for GPT-2-124M), so int8 storage
            # halves the dominant HBM stream. Per-(layer, out-channel)
            # absmax scales over the contracted axis; dequant is applied
            # AFTER each dot as a per-column scale (exact factoring: the
            # int values are exact in bf16, products accumulate fp32), so
            # no dequantized weight copy ever materializes. LN params,
            # biases, embed and LM head stay fp.
            def q_left(w3):          # used as h @ W.T: [L, O, I]
                q, s = _absmax_int8(w3, -1)
                return q, jnp.swapaxes(s, -1, -2)     # [L, 1, O]

            def q_right(w3):         # used as h @ W: [L, I, O]
                return _absmax_int8(w3, 1)            # scales [L, 1, O]

            out["qkv_w"], out["qkv_w_s"] = q_left(out["qkv_w"])
            out["lin_w"], out["lin_w_s"] = q_right(out["lin_w"])
            out["f1_w"], out["f1_w_s"] = q_right(out["f1_w"])
            out["f2_w"], out["f2_w_s"] = q_right(out["f2_w"])
        elif mode == "int4":
            # weight-only int4 (reference: Predictor's weight-only int4
            # mode): absmax/7 per (layer, out-channel), two adjacent
            # CONTRACTED-axis nibbles per byte — quartering the int8
            # flavor's dominant stream again. Packing happens AFTER the
            # head-major qkv fuse above, and always along the reduced
            # axis of the absmax, so the pack never straddles a
            # STACKED_PARAM_SPECS 'mp' split: qkv_w/f1_w pack the
            # UNsharded E axis, and lin_w/f2_w shard the packed axis in
            # whole bytes (validated in _weight_shard_mesh /
            # init_serving_mesh). The packed arrays keep the int8
            # flavor's key names, so the sharding table and every
            # downstream consumer (mm_p, tools) see one vocabulary.
            # mm_p never unpacks to a full fp copy: single-device it
            # runs the fused dequant-matmul Pallas kernel, under a mesh
            # a nibble-split XLA dot (see mm_p).
            self._validate_int4_dims()

            def q4_left(w3):         # used as h @ W.T: [L, O, I]
                q, s = _absmax_int4(w3, -1)
                return _pack_int4(q, -1), jnp.swapaxes(s, -1, -2)

            def q4_right(w3):        # used as h @ W: [L, I, O]
                q, s = _absmax_int4(w3, 1)            # scales [L, 1, O]
                return _pack_int4(q, 1), s

            out["qkv_w"], out["qkv_w_s"] = q4_left(out["qkv_w"])
            out["lin_w"], out["lin_w_s"] = q4_right(out["lin_w"])
            out["f1_w"], out["f1_w_s"] = q4_right(out["f1_w"])
            out["f2_w"], out["f2_w_s"] = q4_right(out["f2_w"])
        mesh = env_sig[1]
        if mesh is not None:
            # tensor-parallel placement: commit every stacked array to
            # its declared layout so each device holds ~1/mp of the
            # sharded weight bytes from first dispatch on (no lazy
            # reshard inside the step). An unknown key is a hard error
            # — the runtime twin of tools/check_sharding_spec.py.
            from jax.sharding import NamedSharding
            from ..parallel import _valid_spec
            for k in out:
                spec = STACKED_PARAM_SPECS.get(k)
                if spec is None:
                    raise ValueError(
                        f"stacked param {k!r} has no entry in "
                        "STACKED_PARAM_SPECS — every stacked key needs "
                        "an explicit PartitionSpec (sharded or the "
                        "replicated P()); see "
                        "tools/check_sharding_spec.py")
                if not _valid_spec(out[k], spec, mesh):
                    spec = PartitionSpec()      # indivisible: replicate
                out[k] = jax.device_put(out[k],
                                        NamedSharding(mesh, spec))
        try:
            anchors = [weakref.ref(a) for a in version]
        except TypeError:
            # non-weakrefable leaves (shouldn't happen for jax arrays):
            # degrade to always-rebuild rather than pin
            anchors = [(lambda: None)] * len(version)
        self._stk_cache = (anchors, out, env_sig)
        return out

    def _maybe_quant_head(self, h_arrays):
        """LM-head preparation for plain Linear heads (non-Linear heads
        pass through untouched — call_layerlike path): optional int8
        quant (PADDLE_TPU_DECODE_INT8_HEAD=1 → [W_int8, scales(, bias)]
        with per-out-channel absmax scales, dequant applied after the
        dot by head_logits), then tensor-parallel placement — under a
        weight-shard mesh the weight [E, V], int8 scales [1, V] and
        bias [V] all shard the VOCAB axis, so logits leave the head
        vocab-sharded and GSPMD gathers them only at the argmax /
        sampling reduction. An indivisible vocab stays replicated (the
        per-key fallback, same policy as the layer stack). Cached on
        (quant flag, mesh, weight identity)."""
        from ..nn.layer.common import Linear
        if type(self.head) is not Linear or not h_arrays:
            return h_arrays
        quant = os.environ.get("PADDLE_TPU_DECODE_INT8_HEAD") == "1"
        mesh = self._weight_shard_mesh()
        if not quant and mesh is None:
            return h_arrays
        import weakref
        sig = (quant, mesh)
        cached = getattr(self, "_head_q_cache", None)
        if cached is not None and cached[2] == sig and \
                len(cached[0]) == len(h_arrays) and \
                all(r() is a for r, a in zip(cached[0], h_arrays)):
            return cached[1]
        if quant:
            q, s = _absmax_int8(h_arrays[0], 0)        # weight [E, V]
            out = [q, s] + list(h_arrays[1:])
        else:
            out = list(h_arrays)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from ..parallel import _valid_spec
            placed = []
            for a in out:
                # vocab is the LAST axis of every Linear-head array:
                # weight [E, V], int8 scales [1, V], bias [V]
                spec = PartitionSpec(*([None] * (a.ndim - 1) + ["mp"]))
                if not _valid_spec(a, spec, mesh):
                    spec = PartitionSpec()
                placed.append(jax.device_put(
                    a, NamedSharding(mesh, spec)))
            out = placed
        # key on EVERY source array (a bias-only swap must invalidate,
        # not serve the stale cached bias)
        self._head_q_cache = ([weakref.ref(a) for a in h_arrays], out,
                              sig)
        return out

    def _int8_cache(self) -> bool:
        """Opt-in int8 KV cache (reference: fused_multi_transformer's
        cache_kv int8 serving mode). Decode is bandwidth-bound — int8
        halves the cache bytes streamed per token; rows are absmax-
        quantized per (layer, kv, batch, head, position) with fp32
        scales, dequantized in VMEM by the stacked kernels (row AND
        flat flavors). An explicit ctor kv_quant wins; None defers to
        PADDLE_TPU_DECODE_INT8_CACHE."""
        if self._kv_quant_arg is not None:
            return self._kv_quant_arg == "int8"
        return os.environ.get("PADDLE_TPU_DECODE_INT8_CACHE") == "1"

    def init_cache(self, batch, dtype=None):
        f = self.fmt
        dtype = dtype or self.fmt.qkv_weights[0]._data.dtype
        shape = (f.num_layers, 2, batch, f.num_heads, self.smax,
                 f.head_dim)
        if self._int8_cache():
            # scales keep positions on the LAST axis ([..., 1, Smax]) so
            # the kernel streams them as [1, bk] lane-major blocks
            # (Mosaic-legal; a [bk, 1] lane-1 block is a compile risk).
            # Composes with mp>=2: the shard_map'd stacked kernel reads
            # each device's local heads of both the int8 stack and the
            # scales (r5; previously int8 was refused under a mesh).
            return (jnp.zeros(shape, jnp.int8),
                    jnp.zeros(shape[:4] + (1, self.smax),
                              jnp.float32))
        return jnp.zeros(shape, dtype)

    def init_paged_cache(self, pool, dtype=None):
        """Device arrays for a paged_kv.BlockPool: the ONE kv pool
        {"kv": [L, 2, NB, H, Bt, D]} (+ {"sc": [L, 2, NB, H, 1, Bt]}
        mirrored int8 scales in cache-quant mode). The caller (the
        serving engine) adds the per-slot block tables as "tbl" per
        dispatch — tables are host state, rebuilt from numpy each call,
        while the pool arrays ride donation like the dense cache.

        Under an active mp mesh the pool is laid out head-sharded on
        the 'mp' axis (NamedSharding; axis 3 of both kv and sc) so each
        device holds pool_bytes / mp — the block allocator, tables and
        all scheduler metadata stay replicated host data, so paged
        churn is invisible to the partitioner."""
        f = self.fmt
        dtype = dtype or self.fmt.qkv_weights[0]._data.dtype
        if getattr(pool, "smax", self.smax) != self.smax:
            raise ValueError(
                f"BlockPool was sized for max_seq_len={pool.smax} but "
                f"this decoder's ring capacity is Smax={self.smax} — "
                "the block table has Smax/Bt entries, the two must "
                "agree")
        shape = (f.num_layers, 2, pool.num_blocks, f.num_heads,
                 pool.block_tokens, f.head_dim)
        mesh = self._mesh_mp()
        sharding = None
        if mesh is not None:
            mp = dict(mesh.shape)["mp"]
            if f.num_heads % mp:
                raise ValueError(
                    f"paged KV pool cannot shard: num_heads="
                    f"{f.num_heads} is not divisible by the mesh's mp "
                    f"degree {mp} — the pool shards by head on the "
                    "'mp' axis")
            from jax.sharding import NamedSharding, PartitionSpec as P
            sharding = NamedSharding(
                mesh, P(None, None, None, "mp", None, None))

        def _zeros(shp, dt):
            z = jnp.zeros(shp, dt)
            return jax.device_put(z, sharding) if sharding is not None \
                else z
        if self._int8_cache():
            return {"kv": _zeros(shape, jnp.int8),
                    "sc": _zeros(shape[:4] + (1, pool.block_tokens),
                                 jnp.float32)}
        return {"kv": _zeros(shape, dtype)}

    # ------------------------------------------------------------ the step
    def _mesh_mp(self):
        from ..parallel import current_mesh
        mesh = current_mesh()
        if mesh is not None and dict(mesh.shape).get("mp", 1) >= 2:
            return mesh
        return None

    def _build_scan_step(self, do_sample, top_k, top_p, temperature,
                         chunk, eos, min_length=0, repetition_penalty=1.0):
        """chunk tokens per device program: lax.scan over the per-token
        step, KV cache + last token + finished mask in the carry. One host
        dispatch per chunk instead of per token — the decode-side analogue
        of jit.run_steps. eos is static (baked into the trace): finished rows keep
        emitting eos on-device. min_length / repetition_penalty apply
        inside the compiled step (reference: generation's logit
        processors); ONLY repetition_penalty needs the [B, V]
        context-presence mask in the carry — min_length alone just
        compares the generated count against the eos column."""
        core = self._build_step_core(do_sample, top_k, top_p, temperature)
        rep_on = repetition_penalty != 1.0
        pen_on = bool(min_length) or rep_on
        hidden, head_logits = core.hidden, core.head_logits

        def next_token(stk, e_arrays, h_arrays, caches, tok, t, key,
                       presence, nt):
            if not pen_on:
                return core(stk, e_arrays, h_arrays, caches, tok, t, key)
            x, caches = hidden(stk, e_arrays, caches, tok, t)
            logits = head_logits(h_arrays, x)
            logits = logits.reshape(logits.shape[0], -1)
            logits = _penalize(logits, presence if rep_on else None,
                               repetition_penalty, nt, min_length, eos)
            return _sample_next(logits, do_sample, top_k, top_p,
                                temperature, key), caches

        def scan_step(stk, e_arrays, h_arrays, caches, tok, t0, keys,
                      finished, presence=None, nt0=None):
            carry0 = (tok, caches, finished) + (
                (presence,) if rep_on else ())

            def body(carry, xs):
                tok, caches, finished = carry[:3]
                presence = carry[3] if rep_on else None
                i, key = xs
                nxt, caches = next_token(
                    stk, e_arrays, h_arrays, caches, tok, t0 + i, key,
                    presence, (nt0 + i) if pen_on else None)
                if eos is not None:
                    nxt = jnp.where(finished, eos, nxt)
                    finished = finished | (nxt == eos)
                out = (nxt, caches, finished)
                if rep_on:
                    out += (presence.at[jnp.arange(nxt.shape[0]),
                                        nxt].set(True),)
                return out, nxt
            carry, toks = jax.lax.scan(
                body, carry0, (jnp.arange(chunk, dtype=jnp.int32), keys))
            if rep_on:
                return toks, carry[1], carry[2], carry[3]
            return toks, carry[1], carry[2]
        # donate the KV cache (in-place ring update, no per-token copy of
        # the [L,2,B,H,Smax,D] buffer)
        return jax.jit(scan_step, donate_argnums=(3,))

    def _build_prefill_scan(self, chunk):
        """Compiled prefill: scan the HIDDEN core (embed + layers + cache
        write, no LM head / sampling) over `chunk` teacher-forced prompt
        tokens starting at traced offset t0. Returns the last token's
        hidden state + updated caches; the caller applies the head once
        after the final chunk (an eager fused-stack prefill dispatches
        every op from the host). Chunk
        sizes come from the same power-of-two ladder as decode so
        arbitrary prompt lengths reuse a bounded set of compiled
        variants."""
        hidden = self._build_step_core(False, 0, 1.0, 1.0).hidden

        def prefill(stk, e_arrays, caches, toks, t0):
            # toks: [chunk, B] int32 (time-major for the scan)
            def body(carry, xs):
                caches = carry
                tok_i, i = xs
                x, caches = hidden(stk, e_arrays, caches, tok_i, t0 + i)
                return caches, x
            caches, xs_out = jax.lax.scan(
                body, caches, (toks, jnp.arange(chunk, dtype=jnp.int32)))
            return xs_out[-1], caches
        return jax.jit(prefill, donate_argnums=(2,))

    def _build_bulk_prefill(self):
        """Whole-prompt prefill (PADDLE_TPU_BULK_PREFILL=1): ONE jitted
        call embeds the prompt, runs the stack with causal flash, and
        builds the ring cache by PADDING the per-layer K/V scan output to
        Smax — the cache is born in its final buffer (no DUS, no carry,
        nothing for copy-insertion to get wrong). One executable per
        exact prompt length (serving should bucket prompts; the chunked
        per-token prefill remains the default). Composes with the int8
        cache (vectorized absmax quant of the whole stack) and int8
        weight stacks (mm handles them)."""
        bulk_hidden = self._build_step_core(False, 0, 1.0, 1.0).bulk_hidden
        smax = self.smax
        cache_dtype = self.fmt.qkv_weights[0]._data.dtype
        int8 = self._int8_cache()

        def prefill(stk, e_arrays, toks):
            x_all, kv_all = bulk_hidden(stk, e_arrays, toks)
            last_x = x_all[:, -1:]
            S = toks.shape[1]
            pad = [(0, 0)] * 4 + [(0, smax - S), (0, 0)]
            if int8:
                q_i8, sc = _absmax_int8(kv_all, -1)
                caches = (jnp.pad(q_i8, pad),
                          jnp.pad(jnp.swapaxes(sc, -1, -2),
                                  [(0, 0)] * 5 + [(0, smax - S)]))
            else:
                caches = jnp.pad(kv_all.astype(cache_dtype), pad)
            return last_x, caches
        return jax.jit(prefill)

    def _build_head_sample(self, do_sample, top_k, top_p, temperature,
                           eos=None, min_length=0,
                           repetition_penalty=1.0):
        """Jitted LM head + filter + sample on one hidden state [B,1,E];
        with penalties active the logit controls apply at nt=0 (prompt
        presence only when repetition_penalty is on). min_length is
        consumed as a BOOL here — nt is baked to 0, so every positive
        value behaves identically (callers key their cache that way to
        avoid gratuitous recompiles)."""
        core = self._build_step_core(do_sample, top_k, top_p, temperature)
        rep_on = repetition_penalty != 1.0
        if not min_length and not rep_on:
            return jax.jit(core.sample_head)
        head_logits = core.head_logits

        def head_sample(h_arrays, x, key, presence=None):
            logits = head_logits(h_arrays, x)
            logits = logits.reshape(logits.shape[0], -1)
            logits = _penalize(logits, presence if rep_on else None,
                               repetition_penalty, 0,
                               1 if min_length else 0, eos)
            return _sample_next(logits, do_sample, top_k, top_p,
                                temperature, key)
        return jax.jit(head_sample)

    # ------------------------------------------------- beam over the cache
    # Reference: fluid beam_search op driving generation against
    # fused_multi_transformer's decode cache. The old generate(num_beams)
    # re-ran the full forward on the growing prefix every step (O(S^2)
    # forwards, one executable per prefix length); here the beams SHARE
    # the prefill cache (prefilled once at batch B, then replicated to
    # B*K on the beam axis) and each step's beam reorder is ONE gather on
    # the batch*beam dim of the cache inside the compiled step — one
    # executable total, no prefix re-forward. Sequences are reconstructed
    # host-side by backtracking the recorded (token, parent-beam) lineage
    # (the compiled step never carries the growing sequence).

    def _build_beam_init(self, k, eos, length_penalty):
        """Jitted step 1: prefill hidden state -> logits -> first top-k.
        Mirrors _beam_search's first iteration (scores [0, -inf...] make
        the K picks come from beam 0's distribution)."""
        core = self._build_step_core(False, 0, 1.0, 1.0)
        head_logits = core.head_logits

        def init(h_arrays, last_x):
            logits = head_logits(h_arrays, last_x)
            logits = logits.reshape(logits.shape[0], -1)
            b, v = logits.shape
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            scores0 = jnp.full((b, k), -1e9, jnp.float32).at[:, 0].set(0.0)
            cand = scores0[..., None] + logp[:, None, :]     # [B, K, V]
            top_scores, top_idx = jax.lax.top_k(cand.reshape(b, k * v), k)
            tok = (top_idx % v).astype(jnp.int32)            # [B, K]
            gen_len = jnp.ones((b, k), jnp.int32)
            if eos is not None:
                newly = tok == eos
                pen = gen_len.astype(jnp.float32) ** length_penalty
                fin_score = jnp.where(newly, top_scores / pen, -jnp.inf)
                finished = newly
            else:
                fin_score = jnp.full((b, k), -jnp.inf, jnp.float32)
                finished = jnp.zeros((b, k), bool)
            beam_idx = jnp.zeros((b, k), jnp.int32)
            return (tok, beam_idx, fin_score, finished, top_scores,
                    gen_len)
        return jax.jit(init)

    def _build_beam_scan(self, k, chunk, eos, length_penalty, split=0):
        """chunk beam steps per device program. Carry: (caches, flat tok
        [B*K], scores/finished/gen_len [B,K]); ys: the per-step lineage +
        bookkeeping snapshots the host backtracks over. Semantics match
        _beam_search step-for-step (finished beams continue only with eos
        at zero added score; GNMT length penalty at finish admission).

        split (static): the prompt's KV region [0, split) is IDENTICAL
        across the beams of a batch row forever (written at prefill,
        before beam replication, never re-written), so reordering it is a
        semantic no-op — the per-step beam gather only touches positions
        >= split and writes them back in place (dynamic_update_slice on
        the donated buffer). For long prompts that removes most of the
        reorder's HBM traffic. split is a pow-2 bucket of the prompt
        length so executables stay bounded."""
        core = self._build_step_core(False, 0, 1.0, 1.0)
        hidden = core.hidden
        head_logits = core.head_logits

        def beam_chunk(stk, e_arrays, h_arrays, caches, tok_flat, t0,
                       scores, finished, gen_len):
            b, kk = scores.shape

            def body(carry, i):
                caches, tok_flat, scores, finished, gen_len = carry
                x, caches = hidden(stk, e_arrays, caches, tok_flat,
                                   t0 + i)
                logits = head_logits(h_arrays, x)
                logits = logits.reshape(b * kk, -1)
                v = logits.shape[-1]
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
                logp = logp.reshape(b, kk, v)
                if eos is not None:
                    only_eos = jnp.where(
                        jnp.arange(v)[None, None, :] == eos, 0.0, -jnp.inf)
                    logp = jnp.where(finished[..., None], only_eos, logp)
                cand = scores[..., None] + logp
                top_scores, top_idx = jax.lax.top_k(
                    cand.reshape(b, kk * v), kk)
                beam_idx = top_idx // v                      # [B, K]
                tok = (top_idx % v).astype(jnp.int32)
                # THE cache gather: reorder the batch*beam axis to each
                # winner's parent (both stack and int8 scales), touching
                # only positions >= split (the shared-prompt region needs
                # no reorder — identical rows)
                flat_src = (jnp.arange(b)[:, None] * kk
                            + beam_idx).reshape(-1)

                def reorder(c, pos_axis):
                    if not split:
                        return jnp.take(c, flat_src, axis=2)
                    tail = jax.lax.slice_in_dim(
                        c, split, c.shape[pos_axis], axis=pos_axis)
                    tail = jnp.take(tail, flat_src, axis=2)
                    starts = [0] * c.ndim
                    starts[pos_axis] = split
                    return jax.lax.dynamic_update_slice(
                        c, tail, tuple(starts))
                if isinstance(caches, tuple):
                    # stack positions ride axis 4; scale positions axis 5
                    caches = (reorder(caches[0], 4),
                              reorder(caches[1], 5))
                else:
                    caches = reorder(caches, 4)
                finished = jnp.take_along_axis(finished, beam_idx, 1)
                gen_len = jnp.take_along_axis(gen_len, beam_idx, 1)
                gen_len = jnp.where(finished, gen_len, gen_len + 1)
                scores = top_scores
                if eos is not None:
                    newly = ~finished & (tok == eos)
                    pen = jnp.maximum(gen_len, 1).astype(
                        jnp.float32) ** length_penalty
                    fin_score = jnp.where(newly, scores / pen, -jnp.inf)
                    finished = finished | newly
                else:
                    fin_score = jnp.full((b, kk), -jnp.inf, jnp.float32)
                ys = (tok, beam_idx, fin_score, finished, scores, gen_len)
                return (caches, tok.reshape(-1), scores, finished,
                        gen_len), ys
            (caches, tok_flat, scores, finished, gen_len), ys = \
                jax.lax.scan(
                    body,
                    (caches, tok_flat, scores, finished, gen_len),
                    jnp.arange(chunk, dtype=jnp.int32))
            return caches, tok_flat, scores, finished, gen_len, ys
        return jax.jit(beam_chunk, donate_argnums=(3,))

    def _build_step_core(self, do_sample, top_k, top_p, temperature):
        f = self.fmt
        eps = f.epsilon
        pre_ln = f.normalize_before
        nh, hd = f.num_heads, f.head_dim
        act = f.activation
        smax = self.smax
        use_rotary = self.use_rotary
        rope_base = self.rope_base
        mesh = self._mesh_mp()
        note = self._paths.add     # trace-time record, see step_paths()
        from ..nn.layer.layers import substitute_param_arrays

        def ln(x, s, b):
            mu = jnp.mean(x.astype(jnp.float32), -1, keepdims=True)
            var = jnp.var(x.astype(jnp.float32), -1, keepdims=True)
            out = (x.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + eps)
            return (out * s + b).astype(x.dtype)

        def rope_block(x, tv2):
            # x: [B, Sq, H, D] at per-(row, position) absolute positions
            # tv2 [B, Sq] — ONE rotary implementation for every decode
            # flavor (rope1 below is a rank adapter over it), so the
            # per-token, serving vector-t, and spec-verify block paths
            # cannot drift numerically
            inv = 1.0 / (rope_base ** (jnp.arange(0, hd, 2,
                                                  dtype=jnp.float32) / hd))
            fr = tv2.astype(jnp.float32)[..., None] * inv   # [B, Sq, D/2]
            s = jnp.concatenate([jnp.sin(fr), jnp.sin(fr)], axis=-1)
            c = jnp.concatenate([jnp.cos(fr), jnp.cos(fr)], axis=-1)
            ss = s[:, :, None, :]
            cc = c[:, :, None, :]
            x1 = x[..., : hd // 2]
            x2 = x[..., hd // 2:]
            rot = jnp.concatenate([-x2, x1], axis=-1)
            return (x * cc.astype(x.dtype) + rot * ss.astype(x.dtype))

        def rope1(x, t):
            # x: [B, 1, H, D] at absolute position t — scalar (every row
            # at the same position, the classic decode step) or [B]
            # (per-row positions, the serving engine's ragged slots)
            tv = jnp.asarray(t).astype(jnp.int32)
            tv2 = jnp.broadcast_to(tv.reshape(-1, 1) if tv.ndim
                                   else tv[None, None], (x.shape[0], 1))
            return rope_block(x, tv2)

        def attend(q, caches, l, t):
            # q: [B, Sq, H, D] (Sq == 1 for the classic decode step; the
            # spec-decode verify step passes the whole K+1 block);
            # caches: [L, 2, B, H, Smax, D] (full stack — the kernel
            # addresses layer l via scalar prefetch, zero-copy), (int8
            # stack, fp32 scales) in cache-quant mode, or the PAGED dict
            # {"kv": [L, 2, NB, H, Bt, D](, "sc"), "tbl": [B, Smax/Bt]}
            # — one block pool, per-slot block tables (paged_kv.py).
            # t: scalar OR [B] per-row BASE positions — query row j
            # attends cache positions <= t + j (the stacked kernels'
            # native block-causal semantics: "new tokens attend causally
            # among themselves and fully to the prefix"; the dense
            # fallback builds the same mask per row).
            sq = q.shape[1]
            qt = jnp.swapaxes(q, 1, 2)                  # [B, H, Sq, D]
            tb = jnp.broadcast_to(jnp.asarray(t).astype(jnp.int32),
                                  (q.shape[0],))
            paged = isinstance(caches, dict)
            quant = isinstance(caches, tuple) or (paged and
                                                  "sc" in caches)
            if paged:
                pool_kv, tbl = caches["kv"], caches["tbl"]
                nb = pool_kv.shape[2]
                # the paged kernel gathers K/V through the block table
                # (table rides as scalar prefetch — block ids are data);
                # under a mesh the pool shards by HEAD on 'mp' while the
                # table stays replicated, so each device runs the same
                # kernel over its local heads against the full table
                if (os.environ.get("PADDLE_TPU_STACKED_KERNEL", "1")
                        != "0"):
                    from ..ops.pallas.decode_attention import (
                        decode_attention_paged, decode_attention_paged_i8,
                        paged_i8_is_supported, paged_is_supported)
                    mp = (1 if mesh is None
                          else dict(mesh.shape).get("mp", 1))
                    if mesh is not None and mp >= 2 and nh % mp == 0 \
                            and pool_kv.shape[3] % mp == 0:
                        # head-sharded paged kernel: attention is
                        # embarrassingly parallel over heads, and the
                        # block table addresses the (replicated) NB axis
                        # only, so shard_map over 'mp' needs no
                        # collectives — same escape-from-GSPMD the dense
                        # stacked path uses below
                        lshape = (pool_kv.shape[:3]
                                  + (pool_kv.shape[3] // mp,)
                                  + pool_kv.shape[4:])
                        ok = (paged_i8_is_supported(
                                  (q.shape[0], sq, nh // mp, hd), lshape,
                                  q.dtype) if quant else
                              paged_is_supported(
                                  (q.shape[0], sq, nh // mp, hd), lshape,
                                  q.dtype, cache_dtype=pool_kv.dtype))
                        if ok:
                            from jax import shard_map
                            from jax.sharding import PartitionSpec as SP
                            hsp = SP(None, "mp", None, None)
                            psp = SP(None, None, None, "mp", None, None)
                            if quant:
                                note("attn=paged_i8_pallas_shard_map")
                                fn = shard_map(
                                    decode_attention_paged_i8, mesh=mesh,
                                    in_specs=(hsp, psp, psp, SP(), SP(),
                                              SP()),
                                    out_specs=hsp, check_vma=False)
                                o = fn(qt, pool_kv, caches["sc"], tbl, l,
                                       tb)
                            else:
                                note("attn=paged_pallas_shard_map")
                                fn = shard_map(
                                    decode_attention_paged, mesh=mesh,
                                    in_specs=(hsp, psp, SP(), SP(),
                                              SP()),
                                    out_specs=hsp, check_vma=False)
                                o = fn(qt, pool_kv, tbl, l, tb)
                            return jnp.swapaxes(o, 1, 2)
                    if mesh is None and quant and paged_i8_is_supported(
                            (q.shape[0], sq, nh, hd), pool_kv.shape,
                            q.dtype):
                        note("attn=paged_i8_pallas")
                        o = decode_attention_paged_i8(
                            qt, pool_kv, caches["sc"], tbl, l, tb)
                        return jnp.swapaxes(o, 1, 2)
                    if mesh is None and not quant and paged_is_supported(
                            (q.shape[0], sq, nh, hd), pool_kv.shape,
                            q.dtype, cache_dtype=pool_kv.dtype):
                        note("attn=paged_pallas")
                        o = decode_attention_paged(qt, pool_kv, tbl, l,
                                                   tb)
                        return jnp.swapaxes(o, 1, 2)
                # gather-through-table dense fallback: materialize the
                # row view [2, B, H, Smax, D] from the pool (sentinel
                # entries clamp to an arbitrary block — their positions
                # are >= the row's lens and masked below, exactly like
                # the dense path's stale ring positions)
                note("attn=paged_xla_gather")
                pool_l = jax.lax.dynamic_index_in_dim(pool_kv, l, 0,
                                                      keepdims=False)
                tc = jnp.minimum(tbl, nb - 1)
                kvg = jnp.take(pool_l, tc, axis=1)  # [2, B, Nblk, H, Bt, D]
                kvg = jnp.transpose(kvg, (0, 1, 3, 2, 4, 5)).reshape(
                    2, tbl.shape[0], nh, smax, hd)
                if quant:
                    sc_l = jax.lax.dynamic_index_in_dim(
                        caches["sc"], l, 0, keepdims=False)
                    scg = jnp.take(sc_l, tc, axis=1)  # [2,B,Nblk,H,1,Bt]
                    scg = jnp.transpose(scg, (0, 1, 3, 4, 2, 5)).reshape(
                        2, tbl.shape[0], nh, 1, smax)
                    cache = kvg.astype(jnp.float32) * jnp.swapaxes(
                        scg, -1, -2)
                else:
                    cache = kvg
                s = jnp.einsum("bhqd,bhsd->bhqs", qt.astype(jnp.float32),
                               cache[0].astype(jnp.float32)) * (hd ** -0.5)
                mask = (jnp.arange(smax)[None, None, None, :]
                        <= (tb[:, None, None, None]
                            + jnp.arange(sq)[None, None, :, None]))
                s = jnp.where(mask, s, -1e30)
                p = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("bhqs,bhsd->bhqd", p,
                               cache[1].astype(jnp.float32))
                return jnp.swapaxes(o, 1, 2).astype(q.dtype)
            # escape hatch: PADDLE_TPU_STACKED_KERNEL=0 forces the dense
            # path (the XLA side of the kernel-vs-XLA comparison)
            if os.environ.get("PADDLE_TPU_STACKED_KERNEL", "1") != "0":
                from ..ops.pallas.decode_attention import (
                    decode_attention_stacked, decode_attention_stacked_i8,
                    stacked_i8_is_supported, stacked_is_supported)
                mp = (1 if mesh is None
                      else dict(mesh.shape).get("mp", 1))
                lens = tb
                cshape = (caches[0] if quant else caches).shape
                if mesh is not None and mp >= 2 and nh % mp == 0 \
                        and cshape[3] % mp == 0:
                    # TP-sharded kernel decode (reference: mp-sharded
                    # heads in fused_multi_transformer_op.cu): attention
                    # is embarrassingly parallel over heads, so shard_map
                    # over 'mp' runs the SAME stacked kernel on each
                    # device's local heads — no collectives, no dense
                    # fallback. A pallas_call can't live under GSPMD
                    # auto-partitioning; shard_map is the manual escape.
                    lshape = cshape[:3] + (cshape[3] // mp,) + cshape[4:]
                    ok = (stacked_i8_is_supported(
                              (q.shape[0], sq, nh // mp, hd), lshape,
                              q.dtype) if quant else
                          stacked_is_supported(
                              (q.shape[0], sq, nh // mp, hd), lshape,
                              q.dtype, cache_dtype=caches.dtype))
                    if ok:
                        from jax import shard_map
                        from jax.sharding import PartitionSpec as SP
                        hsp = SP(None, "mp", None, None)
                        csp = SP(None, None, None, "mp", None, None)
                        # check_vma=False: interpret-mode pallas inside
                        # shard_map trips a jax-0.9 check_vma limit
                        # (same workaround the ring path documents); the
                        # kernel has no collectives, so vma checking
                        # buys nothing here
                        if quant:
                            note("attn=stacked_i8_pallas_shard_map")
                            fn = shard_map(
                                decode_attention_stacked_i8, mesh=mesh,
                                in_specs=(hsp, csp, csp, SP(), SP()),
                                out_specs=hsp, check_vma=False)
                            o = fn(qt, caches[0], caches[1], l, lens)
                        else:
                            note("attn=stacked_pallas_shard_map")
                            fn = shard_map(
                                decode_attention_stacked, mesh=mesh,
                                in_specs=(hsp, csp, SP(), SP()),
                                out_specs=hsp, check_vma=False)
                            o = fn(qt, caches, l, lens)
                        return jnp.swapaxes(o, 1, 2)
                if mesh is None and quant and stacked_i8_is_supported(
                        (q.shape[0], sq, nh, hd), caches[0].shape,
                        q.dtype):
                    note("attn=stacked_i8_pallas")
                    o = decode_attention_stacked_i8(qt, caches[0],
                                                    caches[1], l, lens)
                    return jnp.swapaxes(o, 1, 2)
                if mesh is None and not quant and stacked_is_supported(
                        (q.shape[0], sq, nh, hd), caches.shape, q.dtype,
                        cache_dtype=caches.dtype):
                    note("attn=stacked_pallas")
                    o = decode_attention_stacked(qt, caches, l, lens)
                    return jnp.swapaxes(o, 1, 2)
            # dense masked fallback — under a mesh the head dim ('mp')
            # shards this einsum Megatron-style; the layer slice fuses
            # into the einsum operand read (no materialized copy)
            note("attn=dense_xla")
            if quant:
                ci = jax.lax.dynamic_index_in_dim(caches[0], l, 0,
                                                  keepdims=False)
                sc = jax.lax.dynamic_index_in_dim(caches[1], l, 0,
                                                  keepdims=False)
                # scales are [2, B, H, 1, Smax]; transpose the trailing
                # axes to broadcast per-position over D
                cache = ci.astype(jnp.float32) * jnp.swapaxes(sc, -1, -2)
            else:
                cache = jax.lax.dynamic_index_in_dim(caches, l, 0,
                                                     keepdims=False)
            s = jnp.einsum("bhqd,bhsd->bhqs", qt.astype(jnp.float32),
                           cache[0].astype(jnp.float32)) * (hd ** -0.5)
            # block-causal: query row j (token at position t + j) sees
            # cache cols <= t + j; for Sq == 1 this is the classic mask
            mask = (jnp.arange(smax)[None, None, None, :]
                    <= (tb[:, None, None, None]
                        + jnp.arange(sq)[None, None, :, None]))
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqs,bhsd->bhqd", p,
                           cache[1].astype(jnp.float32))
            return jnp.swapaxes(o, 1, 2).astype(q.dtype)

        def mm_p(a, w, s=None):
            # weight-only int8: dot on the exact int-valued weights
            # (bf16-exact in [-127, 127], fp32 accumulation), then
            # the per-out-channel dequant scale on the [B, O] result.
            # int4 arrives PACKED (two contracted nibbles per int8
            # byte), unambiguous by shape: a packed weight's contracted
            # axis is HALF the activation's — an unpacked int8 weight
            # always matches it exactly.
            if s is not None and w.dtype == jnp.int8 \
                    and 2 * w.shape[0] == a.shape[-1]:
                k2 = w.shape[0]
                if mesh is None:
                    from ..ops.pallas.fused_dequant_matmul import (
                        fused_dequant_matmul,
                        fused_dequant_matmul_is_supported)
                    m_rows = 1
                    for d_ in a.shape[:-1]:
                        m_rows *= d_
                    if fused_dequant_matmul_is_supported(
                            m_rows, a.shape[-1], w.shape[1],
                            a.dtype.itemsize):
                        # fused dequant-matmul: bytes stream packed,
                        # nibbles unpack in VMEM, scales fold into the
                        # fp32 accumulator — no unpacked weight copy
                        note("mm=int4_fused_pallas")
                        return fused_dequant_matmul(
                            a, w, s.reshape(1, -1), out_dtype=a.dtype)
                # nibble-split XLA dot (mesh path — a pallas_call
                # cannot live under GSPMD auto-partitioning — and the
                # unsupported-shape fallback): two half-K dots on the
                # sign-extended nibble planes. The activation splits by
                # a [..., K/2, 2] reshape (GSPMD-representable on a
                # row-sharded axis; a stride-2 slice is not), the
                # weight stays packed — still no full unpacked copy at
                # rest, only the in-fusion nibble views.
                note("mm=int4_nibble_xla")
                lo = jnp.right_shift(jnp.left_shift(w, 4), 4)
                hi = jnp.right_shift(w, 4)
                ar = a.reshape(a.shape[:-1] + (k2, 2))
                out_ = (ar[..., 0] @ lo.astype(a.dtype)
                        + ar[..., 1] @ hi.astype(a.dtype))
                return out_ * s.astype(a.dtype)
            out_ = a @ w.astype(a.dtype)
            return out_ * s.astype(a.dtype) if s is not None else out_

        def qkv_of(h, p):
            # [B, T, E] -> q, k, v [B, T, nh, hd]. Both weight flavors
            # arrive pre-fused HEAD-MAJOR from _stacked ([F, E] with
            # F = nh*3*hd, nh outermost), so one branch serves fp and
            # int8, and the unfuse reshape below keeps the head axis
            # outermost — under tensor parallel the fused axis carries
            # the 'mp' head shard straight through to q/k/v without a
            # weight gather.
            qkv = mm_p(h, p["qkv_w"].T, p.get("qkv_w_s")) + \
                p["qkv_b"].astype(h.dtype)
            qkv = qkv.reshape(h.shape[0], h.shape[1], nh, 3, hd)
            return qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]

        def proj_ffn_tail(residual, attn_flat, p):
            # shared post-attention half of a layer: out-proj + residual
            # + (post-)LN + FFN — shape-agnostic over the token dim, so
            # the per-token step and bulk prefill cannot diverge
            attn = mm_p(attn_flat, p["lin_w"], p.get("lin_w_s")) + \
                p["lin_b"].astype(attn_flat.dtype)
            x = residual + attn
            if not pre_ln:
                x = ln(x, p["ln_s"], p["ln_b"])
            residual = x
            h = ln(x, p["fln_s"], p["fln_b"]) if pre_ln else x
            h = mm_p(h, p["f1_w"], p.get("f1_w_s")) + \
                p["f1_b"].astype(h.dtype)
            h = getattr(jax.nn, act)(h)
            h = mm_p(h, p["f2_w"], p.get("f2_w_s")) + \
                p["f2_b"].astype(h.dtype)
            x = residual + h
            if not pre_ln:
                x = ln(x, p["fln_s"], p["fln_b"])
            return x

        def _write_targets(t, b, write_mask):
            # per-row write positions ([B] int32). Masked-out rows are
            # sent OUT OF BOUNDS (index Smax) so the scatter with
            # mode="drop" skips them entirely — a neighbouring slot's
            # live cache row cannot be touched by construction (the
            # serving engine's in-slot prefill depends on this).
            tv = jnp.broadcast_to(jnp.asarray(t).astype(jnp.int32), (b,))
            if write_mask is not None:
                tv = jnp.where(write_mask, tv, smax)
            return tv

        def _paged_blk_off(tbl, tv, nb):
            # resolve positions tv ([B] or [B, Sq]) through the block
            # table: OOB positions (== smax, the write-mask discipline)
            # and unmapped sentinel entries both land on block `nb` —
            # out of bounds for the pool's block axis, so the scatter
            # with mode="drop" skips them. This is the FIFTH client of
            # the decode_attention `cache_lens < Smax` clamp inventory.
            bt = smax // tbl.shape[1]
            nblk = tbl.shape[1]
            ji = tv // bt
            safe = ji < nblk
            jc = jnp.minimum(ji, nblk - 1)
            if tv.ndim == 1:
                blk = jnp.take_along_axis(tbl, jc[:, None], axis=1)[:, 0]
            else:
                blk = jnp.take_along_axis(tbl, jc, axis=1)
            return jnp.where(safe, blk, nb), tv % bt

        def paged_write(caches, l, tv, kv_new):
            # scatter the new K/V rows through the block table. kv_new:
            # [2, B, H, Sq, D]; tv: [B] (Sq == 1, per-token step) or
            # [B, Sq] (spec-verify block). Same value layouts as the
            # dense per-row scatters, with (block, offset) replacing
            # the ring position.
            pool_kv, tbl = caches["kv"], caches["tbl"]
            nb = pool_kv.shape[2]
            blk, off = _paged_blk_off(tbl, tv, nb)
            if "sc" in caches:
                q_new, sc_new = _absmax_int8(kv_new, -1)
                if tv.ndim == 1:
                    kvq = pool_kv.at[l, :, blk, :, off, :].set(
                        jnp.swapaxes(q_new[:, :, :, 0], 0, 1),
                        mode="drop")
                    scq = caches["sc"].at[l, :, blk, :, 0, off].set(
                        jnp.swapaxes(sc_new[:, :, :, 0, 0], 0, 1),
                        mode="drop")
                else:
                    kvq = pool_kv.at[l, :, blk, :, off, :].set(
                        jnp.transpose(q_new, (1, 3, 0, 2, 4)),
                        mode="drop")
                    scq = caches["sc"].at[l, :, blk, :, 0, off].set(
                        jnp.transpose(sc_new[..., 0], (1, 3, 0, 2)),
                        mode="drop")
                return dict(caches, kv=kvq, sc=scq)
            vals = (jnp.swapaxes(kv_new[:, :, :, 0], 0, 1)
                    if tv.ndim == 1
                    else jnp.transpose(kv_new, (1, 3, 0, 2, 4)))
            return dict(caches, kv=pool_kv.at[l, :, blk, :, off, :].set(
                vals.astype(pool_kv.dtype), mode="drop"))

        def layer_step(x, p, caches, l, t, write_mask=None):
            # one gate for both cache flavors' fused write+attend branch.
            # A masked write (serving's in-slot prefill: only admitted
            # rows may land K/V) always takes the scatter path — the
            # write kernels land every row unconditionally.
            kw_on = (os.environ.get("PADDLE_TPU_KERNEL_CACHE_WRITE",
                                    "0") == "1"
                     and os.environ.get("PADDLE_TPU_STACKED_KERNEL",
                                        "1") != "0"
                     and mesh is None and write_mask is None)
            residual = x
            h = ln(x, p["ln_s"], p["ln_b"]) if pre_ln else x
            b = h.shape[0]
            q, k, v = qkv_of(h, p)
            if use_rotary:
                q = rope1(q, t)
                k = rope1(k, t)
            # write-then-attend: ONE tiny [1, 2, B, H, 1, D] in-place
            # update at (l, :, :, :, t, :) on the scan-carried buffer —
            # the full stack is never copied per step (the old layout
            # emitted the updated cache as stacked scan ys, rewriting the
            # entire [L, 2, B, H, Smax, D] buffer every token)
            kv_new = jnp.stack([jnp.swapaxes(k, 1, 2),
                                jnp.swapaxes(v, 1, 2)])  # [2, B, H, 1, D]
            if isinstance(caches, dict):
                # paged: the K/V row scatters through the slot's block
                # table (write-then-attend, like every other flavor);
                # the fused write+attend kernels stay dense-only — the
                # paged read kernel gathers through the table instead
                caches = paged_write(caches, l,
                                     _write_targets(t, b, write_mask),
                                     kv_new)
                attn = attend(q, caches, l, t)
            elif isinstance(caches, tuple):
                attn = None
                if kw_on:
                    # fused write+attend, int8 flavor: quantizes the new
                    # row IN KERNEL (bit-identical recipe) and lands row
                    # + scale in place — no XLA DUS on either carried
                    # buffer (see the fp branch below for why)
                    from ..ops.pallas.decode_attention import (
                        decode_attention_stacked_i8_write,
                        stacked_i8_write_is_supported)
                    if stacked_i8_write_is_supported(
                            (q.shape[0], 1, nh, hd), caches[0].shape,
                            q.dtype):
                        lens_ = jnp.broadcast_to(
                            jnp.asarray(t).astype(jnp.int32),
                            (q.shape[0],))
                        note("attn=stacked_i8_write_pallas")
                        ci8, scs, o = decode_attention_stacked_i8_write(
                            jnp.swapaxes(q, 1, 2), kv_new, caches[0],
                            caches[1], l, lens_)
                        caches = (ci8, scs)
                        attn = jnp.swapaxes(o, 1, 2)
                if attn is None:
                    # cache-quant write: per-row absmax int8 + fp32 scale
                    q_new, sc_new = _absmax_int8(kv_new, -1)
                    if jnp.ndim(t) == 0 and write_mask is None:
                        ci8 = jax.lax.dynamic_update_slice(
                            caches[0], q_new[None], (l, 0, 0, 0, t, 0))
                        # scale layout is [L, 2, B, H, 1, Smax]: position
                        # on the last axis, so this token's scales land
                        # at [..., 0, t]
                        scs = jax.lax.dynamic_update_slice(
                            caches[1], sc_new[None], (l, 0, 0, 0, 0, t))
                    else:
                        # per-row positions (serving): one scatter of B
                        # rows; masked/OOB rows are dropped
                        tv = _write_targets(t, b, write_mask)
                        bi = jnp.arange(b)
                        ci8 = caches[0].at[l, :, bi, :, tv, :].set(
                            jnp.swapaxes(q_new[:, :, :, 0], 0, 1),
                            mode="drop")
                        scs = caches[1].at[l, :, bi, :, 0, tv].set(
                            jnp.swapaxes(sc_new[:, :, :, 0, 0], 0, 1),
                            mode="drop")
                    caches = (ci8, scs)
                    attn = attend(q, caches, l, t)
            else:
                attn = None
                if kw_on:
                    # fused write+attend: the kernel lands the new K/V
                    # row in place (input_output_aliases) and attends in
                    # one pass — no XLA-side dynamic_update_slice on the
                    # scan carry, so copy-insertion can never
                    # materialize a full-cache copy
                    from ..ops.pallas.decode_attention import (
                        decode_attention_stacked_write,
                        stacked_write_is_supported)
                    if stacked_write_is_supported(
                            (q.shape[0], 1, nh, hd), caches.shape,
                            q.dtype, cache_dtype=caches.dtype):
                        lens_ = jnp.broadcast_to(
                            jnp.asarray(t).astype(jnp.int32),
                            (q.shape[0],))
                        note("attn=stacked_write_pallas")
                        caches, o = decode_attention_stacked_write(
                            jnp.swapaxes(q, 1, 2),
                            kv_new.astype(caches.dtype), caches, l,
                            lens_)
                        attn = jnp.swapaxes(o, 1, 2)
                if attn is None:
                    if jnp.ndim(t) == 0 and write_mask is None:
                        caches = jax.lax.dynamic_update_slice(
                            caches, kv_new[None].astype(caches.dtype),
                            (l, 0, 0, 0, t, 0))
                    else:
                        tv = _write_targets(t, b, write_mask)
                        caches = caches.at[
                            l, :, jnp.arange(b), :, tv, :].set(
                            jnp.swapaxes(kv_new[:, :, :, 0], 0, 1).astype(
                                caches.dtype), mode="drop")
                    attn = attend(q, caches, l, t)
            return proj_ffn_tail(residual, attn.reshape(b, 1, nh * hd),
                                 p), caches

        def spec_layer_step(x, p, caches, l, lens, wmask):
            # one layer of the speculative-decoding VERIFY block: Sq =
            # K+1 tokens land their K/V at per-(row, offset) positions
            # lens[b] + j (write-then-attend, like the per-token step),
            # then ONE block-causal attend covers prefix + draft — the
            # whole block costs one weight stream instead of K+1 scan
            # iterations. wmask [B, Sq]: masked positions scatter out of
            # bounds and are dropped (same discipline as the masked-scan
            # prefill), so a draft past the ring clamp or an inactive
            # slot can never write; their garbage logits are discarded
            # by the host and their cache positions are rewritten before
            # ever becoming attendable (write-then-attend at the next
            # step's advanced lens).
            residual = x
            h = ln(x, p["ln_s"], p["ln_b"]) if pre_ln else x
            b, kp = h.shape[0], h.shape[1]
            q, k, v = qkv_of(h, p)
            offs = jnp.arange(kp, dtype=jnp.int32)[None, :]
            t2 = lens[:, None] + offs                       # [B, Sq]
            if use_rotary:
                q = rope_block(q, t2)
                k = rope_block(k, t2)
            kv_new = jnp.stack([jnp.swapaxes(k, 1, 2),
                                jnp.swapaxes(v, 1, 2)])  # [2, B, H, Sq, D]
            tv = jnp.where(wmask, t2, smax)              # OOB -> dropped
            bi = jnp.arange(b)[:, None]
            if isinstance(caches, dict):
                # paged verify writes: the whole K+1 block scatters
                # through the block table (masked positions -> the
                # sentinel block, dropped — same discipline as dense)
                caches = paged_write(caches, l, tv, kv_new)
                attn = attend(q, caches, l, lens)
                return proj_ffn_tail(
                    residual, attn.reshape(b, kp, nh * hd), p), caches
            if isinstance(caches, tuple):
                q_new, sc_new = _absmax_int8(kv_new, -1)
                ci8 = caches[0].at[l, :, bi, :, tv, :].set(
                    jnp.transpose(q_new, (1, 3, 0, 2, 4)), mode="drop")
                scs = caches[1].at[l, :, bi, :, 0, tv].set(
                    jnp.transpose(sc_new[..., 0], (1, 3, 0, 2)),
                    mode="drop")
                caches = (ci8, scs)
            else:
                caches = caches.at[l, :, bi, :, tv, :].set(
                    jnp.transpose(kv_new, (1, 3, 0, 2, 4)).astype(
                        caches.dtype), mode="drop")
            attn = attend(q, caches, l, lens)
            return proj_ffn_tail(residual, attn.reshape(b, kp, nh * hd),
                                 p), caches

        def flat_write(caches, l, tslot, tpos, kv_new, b):
            # scatter the flat stream's K/V rows to (slot, pos) — the
            # SEVENTH `cache_lens < Smax` clamp client (see
            # decode_attention.py's inventory): pad tokens carry the
            # slot SENTINEL b, which resolves to an out-of-bounds batch
            # index (dense) or the pool's sentinel block (paged), and
            # mode="drop" skips them; real positions are < Smax by the
            # packer's budget arithmetic. kv_new: [2, 1, H, T, D].
            vals = jnp.transpose(kv_new[:, 0], (2, 0, 1, 3))  # [T,2,H,D]
            if isinstance(caches, dict):
                pool_kv, tbl = caches["kv"], caches["tbl"]
                nb = pool_kv.shape[2]
                bt = pool_kv.shape[4]
                nblk = tbl.shape[1]
                ji = tpos // bt
                safe = (tslot < b) & (ji < nblk)
                rows = jnp.take(tbl, jnp.minimum(tslot, b - 1), axis=0)
                blk = jnp.take_along_axis(
                    rows, jnp.minimum(ji, nblk - 1)[:, None],
                    axis=1)[:, 0]
                blk = jnp.where(safe, blk, nb)
                off = tpos % bt
                if "sc" in caches:
                    q_new, sc_new = _absmax_int8(kv_new, -1)
                    kvq = pool_kv.at[l, :, blk, :, off, :].set(
                        jnp.transpose(q_new[:, 0], (2, 0, 1, 3)),
                        mode="drop")
                    scq = caches["sc"].at[l, :, blk, :, 0, off].set(
                        jnp.transpose(sc_new[:, 0, :, :, 0], (2, 0, 1)),
                        mode="drop")
                    return dict(caches, kv=kvq, sc=scq)
                return dict(caches, kv=pool_kv.at[
                    l, :, blk, :, off, :].set(
                    vals.astype(pool_kv.dtype), mode="drop"))
            sl = jnp.minimum(tslot, b - 1)
            tv = jnp.where(tslot < b, tpos, smax)    # OOB -> dropped
            if isinstance(caches, tuple):
                q_new, sc_new = _absmax_int8(kv_new, -1)
                ci8 = caches[0].at[l, :, sl, :, tv, :].set(
                    jnp.transpose(q_new[:, 0], (2, 0, 1, 3)),
                    mode="drop")
                scs = caches[1].at[l, :, sl, :, 0, tv].set(
                    jnp.transpose(sc_new[:, 0, :, :, 0], (2, 0, 1)),
                    mode="drop")
                return (ci8, scs)
            return caches.at[l, :, sl, :, tv, :].set(
                vals.astype(caches.dtype), mode="drop")

        def flat_attend_seg(q_s, caches, l, sslot, spos, cmeta, b):
            # the SEGMENT region's ragged block-flash attend: q_s
            # [Ts, H, D] — aligned single-slot chunks of prefill /
            # draft segments; each token attends its OWN slot's cache
            # positions <= its position. Paged pools take the flat
            # Pallas kernel in BOTH flavors — fp pools the fp kernel,
            # int8 pools decode_attention_paged_flat_i8 (in-kernel
            # dequant of the pool + its mirrored scales; per-chunk
            # metadata rides as scalar prefetch, and under a mesh
            # either flavor runs per-shard via shard_map over the head
            # axis); everything else (dense rings, unsupported shapes,
            # opt-out) goes through the gather-through-table dense
            # fallback — the parity path.
            ts_ = q_s.shape[0]
            paged = isinstance(caches, dict)
            quant = isinstance(caches, tuple) or (paged and
                                                  "sc" in caches)
            if paged:
                pool_kv, tbl = caches["kv"], caches["tbl"]
                if (os.environ.get("PADDLE_TPU_STACKED_KERNEL", "1")
                        != "0"):
                    from ..ops.pallas.decode_attention import (
                        decode_attention_paged_flat,
                        decode_attention_paged_flat_i8,
                        paged_flat_i8_is_supported,
                        paged_flat_is_supported)
                    mp = (1 if mesh is None
                          else dict(mesh.shape).get("mp", 1))
                    if mesh is not None and mp >= 2 and nh % mp == 0 \
                            and pool_kv.shape[3] % mp == 0:
                        # head-sharded flat kernel: per-chunk metadata
                        # and the block table are replicated, the pool
                        # (and in cache-quant mode its scales) shards
                        # by head — shard_map over 'mp' with no
                        # collectives (see attend() for the rationale)
                        lshape = (pool_kv.shape[:3]
                                  + (pool_kv.shape[3] // mp,)
                                  + pool_kv.shape[4:])
                        ok = (paged_flat_i8_is_supported(
                                  ts_, nh // mp, hd, lshape, q_s.dtype)
                              if quant else
                              paged_flat_is_supported(
                                  ts_, nh // mp, hd, lshape, q_s.dtype,
                                  cache_dtype=pool_kv.dtype))
                        if ok:
                            cslot, cbase, cn = cmeta
                            from jax import shard_map
                            from jax.sharding import PartitionSpec as SP
                            qsp = SP(None, "mp", None)
                            psp = SP(None, None, None, "mp", None, None)
                            if quant:
                                note("attn=paged_flat_i8_pallas_shard_map")
                                fn = shard_map(
                                    decode_attention_paged_flat_i8,
                                    mesh=mesh,
                                    in_specs=(qsp, psp, psp, SP(), SP(),
                                              SP(), SP(), SP()),
                                    out_specs=qsp, check_vma=False)
                                return fn(q_s, pool_kv, caches["sc"],
                                          tbl, jnp.minimum(cslot, b - 1),
                                          cbase, cn, l)
                            note("attn=paged_flat_pallas_shard_map")
                            fn = shard_map(
                                decode_attention_paged_flat, mesh=mesh,
                                in_specs=(qsp, psp,
                                          SP(), SP(), SP(), SP(), SP()),
                                out_specs=qsp,
                                check_vma=False)
                            o = fn(q_s, pool_kv, tbl,
                                   jnp.minimum(cslot, b - 1), cbase, cn,
                                   l)
                            return o
                    if mesh is None and quant and \
                            paged_flat_i8_is_supported(
                                ts_, nh, hd, pool_kv.shape, q_s.dtype):
                        cslot, cbase, cn = cmeta
                        note("attn=paged_flat_i8_pallas")
                        return decode_attention_paged_flat_i8(
                            q_s, pool_kv, caches["sc"], tbl,
                            jnp.minimum(cslot, b - 1), cbase, cn, l)
                    if mesh is None and not quant and \
                            paged_flat_is_supported(
                                ts_, nh, hd, pool_kv.shape, q_s.dtype,
                                cache_dtype=pool_kv.dtype):
                        cslot, cbase, cn = cmeta
                        note("attn=paged_flat_pallas")
                        o = decode_attention_paged_flat(
                            q_s, pool_kv, tbl,
                            jnp.minimum(cslot, b - 1), cbase, cn, l)
                        return o
                from .paged_kv import flat_gather_view
                note("attn=paged_flat_xla_gather")
                pool_l = jax.lax.dynamic_index_in_dim(pool_kv, l, 0,
                                                      keepdims=False)
                sc_l = (jax.lax.dynamic_index_in_dim(
                    caches["sc"], l, 0, keepdims=False)
                    if quant else None)
                kvg = flat_gather_view(pool_l, tbl,
                                       jnp.minimum(sslot, b - 1),
                                       smax, sc_l)  # [2,Ts,H,Smax,D]
            else:
                note("attn=dense_flat_xla")
                sl = jnp.minimum(sslot, b - 1)
                if quant:
                    ci = jax.lax.dynamic_index_in_dim(caches[0], l, 0,
                                                      keepdims=False)
                    sc = jax.lax.dynamic_index_in_dim(caches[1], l, 0,
                                                      keepdims=False)
                    kvg = (jnp.take(ci, sl, axis=1).astype(jnp.float32)
                           * jnp.swapaxes(jnp.take(sc, sl, axis=1),
                                          -1, -2))
                else:
                    cache_l = jax.lax.dynamic_index_in_dim(
                        caches, l, 0, keepdims=False)
                    kvg = jnp.take(cache_l, sl, axis=1).astype(
                        jnp.float32)
            s_ = jnp.einsum("thd,thsd->ths",
                            q_s.astype(jnp.float32), kvg[0]) \
                * (hd ** -0.5)
            mask = (jnp.arange(smax)[None, None, :]
                    <= spos[:, None, None])
            s_ = jnp.where(mask, s_, -1e30)
            p = jax.nn.softmax(s_, axis=-1)
            o = jnp.einsum("ths,thsd->thd", p, kvg[1])
            return o.astype(q_s.dtype)

        def flat_layer_step(x, p, caches, l, tslot, tpos, cmeta, b):
            # one layer of the FLAT budget core: the whole ragged [T]
            # stream runs the dense ops as one [1, T, E] pass (T real
            # tokens cost T positions — no [B, C] row padding), K/V
            # scatters to (slot, pos), then attention splits by region:
            # tokens [0, b) are the DECODE region (token i IS slot i —
            # the existing per-token kernels serve it unchanged), the
            # rest are aligned segments through flat_attend_seg.
            residual = x
            h = ln(x, p["ln_s"], p["ln_b"]) if pre_ln else x
            t_all = h.shape[1]
            q, k, v = qkv_of(h, p)                  # [1, T, H, D]
            if use_rotary:
                q = rope_block(q, tpos[None, :])
                k = rope_block(k, tpos[None, :])
            kv_new = jnp.stack([jnp.swapaxes(k, 1, 2),
                                jnp.swapaxes(v, 1, 2)])  # [2,1,H,T,D]
            caches = flat_write(caches, l, tslot, tpos, kv_new, b)
            qd = q[0, :b][:, None]                  # [b, 1, H, D]
            ad = attend(qd, caches, l, tpos[:b])    # [b, 1, H, D]
            parts = [jnp.swapaxes(ad, 0, 1).reshape(1, b, nh * hd)]
            if t_all > b:
                a_s = flat_attend_seg(q[0, b:], caches, l, tslot[b:],
                                      tpos[b:], cmeta, b)
                parts.append(a_s.reshape(1, t_all - b, nh * hd))
            attn = (jnp.concatenate(parts, axis=1)
                    if len(parts) > 1 else parts[0])
            return proj_ffn_tail(residual, attn, p), caches

        embed, head = self.embed, self.head
        e_params, h_params = self._embed_params, self._head_params

        def call_layerlike(fn, params, arrays, x_arr):
            # no_grad: inference-only — must not record onto (or clear!) a
            # caller's pending autograd tape
            with substitute_param_arrays(params, arrays), no_grad():
                out = fn(Tensor(x_arr))
            return out._data if isinstance(out, Tensor) else out

        def shard_caches(caches):
            # pin the carried cache sharding under a mesh so the
            # scan-carried buffer (and its donation round-trip) keeps a
            # stable layout: dense rings / int8 stacks AND the paged
            # pool's kv/sc shard by HEAD on 'mp' (axis 3 in every
            # layout); the paged block table is replicated host
            # metadata re-uploaded per dispatch
            if mesh is None:
                return caches
            from jax.sharding import NamedSharding, PartitionSpec as P
            sh = NamedSharding(mesh,
                               P(None, None, None, "mp", None, None))
            if isinstance(caches, dict):
                out = dict(caches)
                out["kv"] = jax.lax.with_sharding_constraint(
                    caches["kv"], sh)
                if "sc" in caches:
                    out["sc"] = jax.lax.with_sharding_constraint(
                        caches["sc"], sh)
                if "tbl" in caches:
                    out["tbl"] = jax.lax.with_sharding_constraint(
                        caches["tbl"], NamedSharding(mesh, P()))
                return out
            if isinstance(caches, tuple):
                return tuple(jax.lax.with_sharding_constraint(c, sh)
                             for c in caches)
            return jax.lax.with_sharding_constraint(caches, sh)

        def hidden(stk, e_arrays, caches, tok, t, write_mask=None):
            # tok: [B] int32; t: scalar int32 OR [B] per-row positions
            # (serving: each slot decodes at its own depth); caches:
            # [L, 2, B, H, Smax, D] -> (x [B, 1, E], caches) with caches
            # updated at position t (rows where write_mask is False are
            # skipped — attention still runs, the K/V write is dropped).
            # The cache rides the layer scan as CARRY (in-place dynamic
            # updates on one buffer), not as xs->ys (which rewrote the
            # whole stack per token — the r3 decode profile's ~10 ms/token
            # vs ~1 ms bandwidth-floor gap).
            x = call_layerlike(embed, e_params, e_arrays, tok[:, None])
            caches = shard_caches(caches)

            def body(carry, xs):
                x, caches = carry
                p, l = xs
                x, caches = layer_step(x, p, caches, l, t, write_mask)
                return (x, caches), None
            nl = (caches["kv"] if isinstance(caches, dict)
                  else caches[0] if isinstance(caches, tuple)
                  else caches).shape[0]
            (x, caches), _ = jax.lax.scan(
                body, (x, caches), (stk, jnp.arange(nl, dtype=jnp.int32)))
            return x, caches

        def spec_hidden(stk, e_arrays, caches, toks, lens, write_mask):
            # toks: [B, Sq] int32 (position 0 the current input token,
            # 1..K the draft); lens: [B] per-row base positions;
            # write_mask: [B, Sq]. Returns (x [B, Sq, E], caches) — the
            # verify-step hidden core: ONE pass of the layer stack over
            # the whole K+1 block (see spec_layer_step).
            x = call_layerlike(embed, e_params, e_arrays, toks)
            caches = shard_caches(caches)

            def body(carry, xs):
                x, caches = carry
                p, l = xs
                x, caches = spec_layer_step(x, p, caches, l, lens,
                                            write_mask)
                return (x, caches), None
            nl = (caches["kv"] if isinstance(caches, dict)
                  else caches[0] if isinstance(caches, tuple)
                  else caches).shape[0]
            (x, caches), _ = jax.lax.scan(
                body, (x, caches), (stk, jnp.arange(nl, dtype=jnp.int32)))
            return x, caches

        def flat_hidden(stk, e_arrays, caches, toks, tslot, tpos, cmeta,
                        b):
            # toks/tslot/tpos: [T] — the flat budget core's ragged
            # token stream ([0, b) decode region + aligned segments);
            # cmeta: per-chunk (slot, base, n) scalar-prefetch metadata
            # for the flat Pallas kernel. Returns (x [1, T, E], caches)
            # with every valid token's K/V landed at (slot, pos).
            x = call_layerlike(embed, e_params, e_arrays, toks[None, :])
            caches = shard_caches(caches)

            def body(carry, xs):
                x, caches = carry
                p, l = xs
                x, caches = flat_layer_step(x, p, caches, l, tslot,
                                            tpos, cmeta, b)
                return (x, caches), None
            nl = (caches["kv"] if isinstance(caches, dict)
                  else caches[0] if isinstance(caches, tuple)
                  else caches).shape[0]
            (x, caches), _ = jax.lax.scan(
                body, (x, caches), (stk, jnp.arange(nl, dtype=jnp.int32)))
            return x, caches

        def head_logits(h_arrays, x_arr):
            # weight-only int8 LM head (PADDLE_TPU_DECODE_INT8_HEAD):
            # h_arrays arrives as [W_int8, scales(, bias...)] from
            # _maybe_quant_head — detect by dtype (trace-time python,
            # retraced per pytree structure) and apply the same
            # dequant-after-dot factoring as the layer stacks. The head
            # read (~[E, V], 77 MB/token bf16 for GPT-2) is the largest
            # single stream of the decode step.
            if h_arrays and getattr(h_arrays[0], "dtype", None) == \
                    jnp.int8:
                w_q, s = h_arrays[0], h_arrays[1]
                out = (x_arr @ w_q.astype(x_arr.dtype)) * \
                    s.astype(x_arr.dtype)
                if len(h_arrays) > 2:
                    out = out + h_arrays[2].astype(out.dtype)
                return out
            return call_layerlike(head, h_params, h_arrays, x_arr)

        def sample_head(h_arrays, x, key):
            logits = head_logits(h_arrays, x)
            logits = logits.reshape(logits.shape[0], -1)
            logits = _filter_logits(logits, do_sample, top_k, top_p,
                                    temperature)
            if do_sample:
                nxt = jax.random.categorical(key, logits, axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            return nxt.astype(jnp.int32)

        def rope_bulk(x, pos):
            # x: [B, S, H, D] at absolute positions pos [S] — the
            # vectorized twin of rope1 (identical math, so bulk prefill
            # writes bit-identical K to the per-token path)
            inv = 1.0 / (rope_base ** (jnp.arange(0, hd, 2,
                                                  dtype=jnp.float32) / hd))
            fr = pos.astype(jnp.float32)[:, None] * inv[None, :]  # [S,D/2]
            s = jnp.concatenate([jnp.sin(fr), jnp.sin(fr)], axis=-1)
            c = jnp.concatenate([jnp.cos(fr), jnp.cos(fr)], axis=-1)
            ss = s[None, :, None, :]
            cc = c[None, :, None, :]
            x1 = x[..., : hd // 2]
            x2 = x[..., hd // 2:]
            rot = jnp.concatenate([-x2, x1], axis=-1)
            return (x * cc.astype(x.dtype) + rot * ss.astype(x.dtype))

        def bulk_hidden(stk, e_arrays, toks):
            """Whole-prompt prefill: embed [B, S], run the layer stack
            with CAUSAL FLASH attention over the full sequence (MXU-fed
            [B,S,E] matmuls instead of the per-token scan's [B,1,E]
            slivers), and return (hidden states [B,S,E],
            kv_all [L,2,B,H,S,D]). The K/V stack comes out as scan ys —
            never a carried buffer — so the caller builds the ring cache
            with ONE pad, no DUS and no aliasing hazard at all. ALL
            positions' hidden states come back (not just the last): the
            serving engine's in-slot bulk admission pads ragged prompts
            to a pow-2 bucket and gathers each row's hidden at its OWN
            last real token."""
            from ..ops.pallas import flash_attention as fa
            x = call_layerlike(embed, e_params, e_arrays, toks)
            S = toks.shape[1]
            pos = jnp.arange(S, dtype=jnp.int32)

            def body(x, p):
                residual = x
                h = ln(x, p["ln_s"], p["ln_b"]) if pre_ln else x
                bsz = h.shape[0]
                q, k, v = qkv_of(h, p)
                if use_rotary:
                    q = rope_bulk(q, pos)
                    k = rope_bulk(k, pos)
                # causal self-attention over the prompt ([B, S, H, D]
                # layout is the flash kernel's own)
                if fa.is_supported(q.shape, q.dtype):
                    o = fa.flash_attention(q, k, v, causal=True)
                else:
                    s_ = jnp.einsum(
                        "bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * (hd ** -0.5)
                    m_ = jnp.tril(jnp.ones((S, S), bool))
                    s_ = jnp.where(m_[None, None], s_, -1e30)
                    o = jnp.einsum("bhqk,bkhd->bqhd",
                                   jax.nn.softmax(s_, axis=-1),
                                   v.astype(jnp.float32)).astype(q.dtype)
                x = proj_ffn_tail(residual, o.reshape(bsz, S, nh * hd),
                                  p)
                kv = jnp.stack([jnp.swapaxes(k, 1, 2),
                                jnp.swapaxes(v, 1, 2)])  # [2, B, H, S, D]
                return x, kv

            x, kv_all = jax.lax.scan(body, x, stk)
            return x, kv_all

        def step(stk, e_arrays, h_arrays, caches, tok, t, key):
            x, caches = hidden(stk, e_arrays, caches, tok, t)
            return sample_head(h_arrays, x, key), caches

        step.hidden = hidden
        step.spec_hidden = spec_hidden
        step.flat_hidden = flat_hidden
        step.bulk_hidden = bulk_hidden
        step.sample_head = sample_head
        step.call_layerlike = call_layerlike
        step.head_logits = head_logits
        return step

    # ------------------------------------------------ speculative decoding
    def _build_verify_core(self, k, rep_on=False, greedy_out=False):
        """The speculative-decoding VERIFY step (Leviathan et al. 2023;
        drafts come from the model-free n-gram lookup in spec_decode.py):
        ONE compiled fixed-shape step runs K+1 positions per row through
        the stack — position 0 is the row's current input token, 1..K the
        draft — using the per-row-position vector-t + write-masked KV
        path (same discipline as the masked-scan prefill; every landed
        write stays under the `cache_lens < Smax` clamp documented in
        decode_attention.py because masked positions scatter out of
        bounds and drop). It returns the PENALIZED logits at all K+1
        positions so acceptance/rollback is pure host data: rejected
        positions' K/V are never attendable (the next step's writes land
        at the advanced lens BEFORE those positions are read —
        write-then-attend), and `cache_lens` advances by accepted+1
        only, entirely host-side.

        Per-slot eos / min_length / repetition_penalty vectorize across
        the block: position j is penalized as the (nt+j)-th generated
        token, with the presence mask speculatively extended by the
        draft tokens consumed at positions <= j (the host discards the
        speculative presence and re-applies only accepted tokens).

        A row with no usable draft rides in as a padded all-masked draft
        (dlen == 0) and the step degrades to the normal decode step for
        that row — one executable for every draft pattern, zero retraces
        across churn. Signature (all [B] unless noted): (stk, e_arrays,
        h_arrays, caches, toks [B, K+1], lens, dlen, active, nt,
        eos_ids, min_len, rep_pen, presence [B, V] or placeholder) ->
        (caches, logits [B, K+1, V]).

        greedy_out=True: greedy acceptance only consumes the argmax
        chain, so the step returns [B, K+1] int32 argmax instead of the
        logits — at production vocab sizes that drops the per-step
        device-to-host transfer from ~MBs to bytes."""
        core = self._build_step_core(False, 0, 1.0, 1.0)
        spec_hidden, head_logits = core.spec_hidden, core.head_logits
        smax = self.smax
        kp = int(k) + 1

        def verify(stk, e_arrays, h_arrays, caches, toks, lens, dlen,
                   active, nt, eos_ids, min_len, rep_pen, presence):
            offs = jnp.arange(kp, dtype=jnp.int32)[None, :]     # [1, Kp]
            t2 = lens[:, None] + offs                           # [B, Kp]
            valid = (active[:, None] & (offs <= dlen[:, None])
                     & (t2 < smax))
            x, caches = spec_hidden(stk, e_arrays, caches, toks, lens,
                                    valid)
            logits = head_logits(h_arrays, x)
            logits = logits.reshape(logits.shape[0], kp, -1)
            v = logits.shape[-1]
            if rep_on:
                # speculative presence: position j's context includes
                # the draft tokens consumed at positions <= j (cumulative
                # one-hot OR, masked to valid positions) on top of the
                # carried presence — matches the sequential step's
                # token-by-token presence updates exactly
                oh = (jax.nn.one_hot(toks, v, dtype=jnp.int32)
                      * valid[..., None].astype(jnp.int32))
                seen = (jnp.cumsum(oh, axis=1) > 0) | presence[:, None, :]
                pen = rep_pen[:, None, None]
                logits = jnp.where(
                    seen,
                    jnp.where(logits > 0, logits / pen, logits * pen),
                    logits)
            cols = jnp.arange(v)[None, None, :]
            is_eos = cols == eos_ids[:, None, None]
            suppress = is_eos & ((nt[:, None] + offs)
                                 < min_len[:, None])[..., None]
            logits = jnp.where(suppress, -1e30, logits)
            if greedy_out:
                return caches, jnp.argmax(logits, axis=-1).astype(
                    jnp.int32)
            return caches, logits
        return verify

    # ------------------------------------------------ token-budget step
    def _build_budget_core(self, c, rep_on=False, do_sample=False,
                           top_k=0, top_p=1.0, temperature=1.0,
                           full_logits=False, chain=False, scan_tail=0):
        """The unified TOKEN-BUDGET step (Sarathi-style chunked prefill:
        every dispatch spends a fixed token budget mixing decode tokens
        and prefill chunks, so a long prompt streams through spare
        capacity instead of holding the decode gang hostage): ONE
        compiled [B, C]-column pass generalizing the spec-verify core to
        per-row SEGMENT lengths. Row b processes `seg[b]` real tokens
        starting at its own base position `lens[b]` — a decode row's
        segment is its current input token plus any draft tokens (spec
        decoding is just another claim on the budget), a prefilling
        row's segment is its next prompt chunk (teacher-forced), an idle
        row ships seg == 0 and rides all-masked. Everything per-row is
        DATA, so one executable covers every packing the scheduler can
        emit — zero retraces across admission/prefill/decode/draft
        churn.

        `gen0[b]` is the column index at which row b's GENERATION
        starts: 0 for decode rows, seg-1 for a prefill row finishing its
        prompt this dispatch (the last prompt token's logits sample the
        first generated token), C (never) for a mid-prompt chunk.
        Position j is penalized as the (nt + max(0, j - gen0))-th
        generated token; columns before gen0 are teacher-forced and
        their outputs are discarded by the host.

        Write discipline is the verify core's: K/V for the whole block
        scatters through `valid = (col < seg) & (pos < Smax)` — masked
        positions go out of bounds and drop (the `cache_lens < Smax`
        clamp inventory in decode_attention.py; this step rides the
        same spec_hidden path as the verify core), then one block-causal
        attend covers prefix + segment.

        Output (by static engine config): without spec (chain=False)
        the ONLY block logits any consumer reads are each row's LAST
        valid column's, so the core gathers that one hidden state per
        row BEFORE the LM head ([B, E] through the head instead of
        [B, C, V] — the head is the largest stream of the step, and at
        C columns the full-chain head would cost C x the decode
        step's), samples [B] tokens (argmax, or _sample_rows in
        sampled mode — scheduling-invariant), and then runs
        `scan_tail` TRAILING DECODE iterations in the SAME dispatch
        (the decode-chunk scan body verbatim): rows that are decoding
        — including a row whose prompt just finished in this very
        block — keep emitting `decode_chunk` tokens per dispatch while
        prefill streams, so mixed steps never slow decode below the
        plain chunk. Returns (caches, tok0 [B], emit0 [B] bool,
        ys (toks, emitted) [scan_tail, B], lens, active, nt, presence)
        with ALL row state advanced on device, like the decode chunk.
        With spec (chain=True) draft acceptance needs all segment
        positions: greedy -> the [B, C] argmax chain, sampled
        (full_logits=True) -> penalized logits [B, C, V] for host-side
        rejection sampling (no trailing scan — accepted drafts already
        make the block multi-token)."""
        from .serving import _penalize_slots
        core = self._build_step_core(False, 0, 1.0, 1.0)
        spec_hidden, head_logits = core.spec_hidden, core.head_logits
        hidden = core.hidden
        smax = self.smax
        c = int(c)
        nscan = int(scan_tail)
        tail = _make_budget_tail(hidden, head_logits, _penalize_slots,
                                 rep_on, do_sample, top_k, top_p,
                                 temperature, nscan)

        def budget(stk, e_arrays, h_arrays, caches, toks, lens, seg,
                   gen0, nt, max_nt, eos_ids, min_len, rep_pen,
                   presence, seeds):
            offs = jnp.arange(c, dtype=jnp.int32)[None, :]      # [1, C]
            t2 = lens[:, None] + offs                           # [B, C]
            valid = (offs < seg[:, None]) & (t2 < smax)
            x, caches = spec_hidden(stk, e_arrays, caches, toks, lens,
                                    valid)
            if not chain:
                # per-row gather at the last valid column, THEN the
                # head: position seg-1 is a row's only consumed block
                # output (its generated-token count there is exactly
                # nt, so the per-slot penalty helper applies verbatim —
                # the head being per-position linear, gather-then-head
                # is bit-identical to head-then-gather)
                last = jnp.maximum(seg - 1, 0)
                xl = jnp.take_along_axis(x, last[:, None, None],
                                         axis=1)
                logits = head_logits(h_arrays, xl)
                logits = logits.reshape(logits.shape[0], -1)
                logits = _penalize_slots(
                    logits, presence if rep_on else None, rep_pen, nt,
                    min_len, eos_ids)
                tok0 = _sample_rows(logits, do_sample, top_k, top_p,
                                    temperature, seeds, nt)
                # block bookkeeping, all vectorized: a row emitted iff
                # its segment reached generation (decode rows always;
                # a prefill row only when the prompt finished here)
                emit0 = (seg > 0) & (gen0 < seg)
                hit_eos = (eos_ids >= 0) & (tok0 == eos_ids)
                lens = lens + seg                # consumed positions
                nt = nt + emit0.astype(jnp.int32)
                active = emit0 & ~hit_eos & (nt < max_nt)
                tok = jnp.where(emit0, tok0, toks[:, 0])
                if rep_on:
                    presence = presence.at[
                        jnp.arange(tok0.shape[0]), tok0].max(emit0)

                (tok, caches, lens, active, nt, presence), ys = tail(
                    stk, e_arrays, h_arrays, tok, caches, lens, active,
                    nt, presence, max_nt, eos_ids, min_len, rep_pen,
                    seeds)
                return (caches, tok0, emit0, ys, tok, lens, active, nt,
                        presence)
            logits = head_logits(h_arrays, x)
            logits = logits.reshape(logits.shape[0], c, -1)
            v = logits.shape[-1]
            if rep_on:
                # speculative presence, as in the verify core: position
                # j's context adds the segment tokens consumed at
                # columns <= j (prompt tokens are already in the carried
                # presence — admission seeds it with the full prompt —
                # so the cumulative OR only really adds draft tokens)
                oh = (jax.nn.one_hot(toks, v, dtype=jnp.int32)
                      * valid[..., None].astype(jnp.int32))
                seen = (jnp.cumsum(oh, axis=1) > 0) | presence[:, None, :]
                pen = rep_pen[:, None, None]
                logits = jnp.where(
                    seen,
                    jnp.where(logits > 0, logits / pen, logits * pen),
                    logits)
            nt_eff = nt[:, None] + jnp.maximum(offs - gen0[:, None], 0)
            cols = jnp.arange(v)[None, None, :]
            is_eos = cols == eos_ids[:, None, None]
            suppress = is_eos & (nt_eff < min_len[:, None])[..., None]
            logits = jnp.where(suppress, -1e30, logits)
            if full_logits:
                return caches, logits
            return caches, jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return budget

    # --------------------------------------------- flat token-budget step
    def _build_flat_budget_core(self, ts, b, rep_on=False,
                                do_sample=False, top_k=0, top_p=1.0,
                                temperature=1.0, full_logits=False,
                                chain=False, scan_tail=0):
        """The TOKEN-FLATTENED budget step (sibling of
        _build_budget_core, Sarathi's token-flattened batch): instead
        of the row-aligned [B, C] block — which computes every masked
        column, wasting (B-1) x C positions on a lone long prefill —
        the dispatch is ONE ragged [T] token stream: T = b + ts, where
        tokens [0, b) are the DECODE REGION (token i is slot i's
        current input when the slot decodes draft-free this dispatch;
        idle slots ride the SENTINEL b) and tokens [b, b+ts) are
        SEGMENTS (prefill chunks, spec draft claims) packed
        back-to-back with starts aligned to decode_attention.FLAT_CHUNK
        so the flat Pallas kernel's query chunks are single-slot. Every
        per-token datum — (slot, pos), segment columns, chunk metadata
        — is DATA; ts comes from the packer's eighth-octave ladder, so
        the executable set is bounded and churn retraces nothing after the
        ladder warms.

        A prefill segment is NOT capped at C columns: one segment can
        span the whole remaining budget, so a long prompt streams
        budget-sized chunks per dispatch instead of C-sized ones — the
        flat layout's second win beyond dropping the row padding.

        K/V writes scatter per token to (slot, pos) with the sentinel/
        OOB drop discipline (the SEVENTH `cache_lens < Smax` clamp
        client — decode_attention.py's inventory); sampling gathers
        each slot's LAST valid hidden state (`last_idx`, the PR 7
        gather-then-head trick generalized from per-row to
        per-segment) before the LM head and draws via _sample_rows
        keyed on fold_in(seed, nt) — per-token, never per-layout, so
        flat outputs are EXACTLY the row core's, greedy and sampled.
        Without spec the same trailing decode scan (`scan_tail`,
        shared builder) follows; with spec (chain=True) the core
        returns the whole stream's argmax chain (or penalized logits
        [T, V] with full_logits) and the host slices each slot's
        segment for acceptance — draft claims are just flat segments.

        Signature (operands beyond the row core's: tslot/tpos [T] the
        per-token indices, cslot/cbase/cn [T/FLAT_CHUNK] the kernel's
        chunk metadata, tcol/tstart [T] per-token segment columns and
        segment-start stream indices for the chain penalties, tok_in/
        last_idx/emit0/adv [B] the per-slot harvest vectors)."""
        from .serving import _penalize_slots
        core = self._build_step_core(False, 0, 1.0, 1.0)
        flat_hidden, head_logits = core.flat_hidden, core.head_logits
        hidden = core.hidden
        b = int(b)
        nscan = int(scan_tail)
        tail = _make_budget_tail(hidden, head_logits, _penalize_slots,
                                 rep_on, do_sample, top_k, top_p,
                                 temperature, nscan)

        def flat_budget(stk, e_arrays, h_arrays, caches, toks, tslot,
                        tpos, cslot, cbase, cn, tcol, tstart, gen0,
                        tok_in, last_idx, emit0, adv, lens, nt, max_nt,
                        eos_ids, min_len, rep_pen, presence, seeds):
            x, caches = flat_hidden(stk, e_arrays, caches, toks, tslot,
                                    tpos, (cslot, cbase, cn), b)
            if not chain:
                # gather-then-head at each slot's last valid stream
                # index (bit-identical to head-then-gather: the head is
                # per-position linear), then the row core's block
                # bookkeeping verbatim — emit0/adv arrive as data from
                # the packer instead of being derived from seg/gen0
                xl = jnp.take(x[0], last_idx, axis=0)[:, None]
                logits = head_logits(h_arrays, xl)
                logits = logits.reshape(logits.shape[0], -1)
                logits = _penalize_slots(
                    logits, presence if rep_on else None, rep_pen, nt,
                    min_len, eos_ids)
                tok0 = _sample_rows(logits, do_sample, top_k, top_p,
                                    temperature, seeds, nt)
                hit_eos = (eos_ids >= 0) & (tok0 == eos_ids)
                lens = lens + adv
                nt = nt + emit0.astype(jnp.int32)
                active = emit0 & ~hit_eos & (nt < max_nt)
                tok = jnp.where(emit0, tok0, tok_in)
                if rep_on:
                    presence = presence.at[
                        jnp.arange(tok0.shape[0]), tok0].max(emit0)
                (tok, caches, lens, active, nt, presence), ys = tail(
                    stk, e_arrays, h_arrays, tok, caches, lens, active,
                    nt, presence, max_nt, eos_ids, min_len, rep_pen,
                    seeds)
                return (caches, tok0, emit0, ys, tok, lens, active, nt,
                        presence)
            # chain: per-token outputs over the whole stream for
            # host-side draft acceptance / prefill first-token reads
            logits = head_logits(h_arrays, x)
            logits = logits.reshape(-1, logits.shape[-1])   # [T, V]
            v = logits.shape[-1]
            cl = jnp.minimum(tslot, b - 1)
            valid = tslot < b
            if rep_on:
                # speculative presence, segment-local: the global
                # cumsum minus its value just before each token's
                # segment start isolates the segment's own tokens
                # (counts are monotone), matching the row core's
                # per-row cumulative OR exactly
                oh = (jax.nn.one_hot(toks, v, dtype=jnp.int32)
                      * valid[:, None].astype(jnp.int32))
                cs = jnp.cumsum(oh, axis=0)
                prev = jnp.where(
                    (tstart > 0)[:, None],
                    jnp.take(cs, jnp.maximum(tstart - 1, 0), axis=0),
                    0)
                seen = ((cs - prev) > 0) | jnp.take(presence, cl,
                                                    axis=0)
                pen = jnp.take(rep_pen, cl)[:, None]
                logits = jnp.where(
                    seen,
                    jnp.where(logits > 0, logits / pen, logits * pen),
                    logits)
            nt_eff = jnp.take(nt, cl) + jnp.maximum(
                tcol - jnp.take(gen0, cl), 0)
            cols = jnp.arange(v)[None, :]
            is_eos = cols == jnp.take(eos_ids, cl)[:, None]
            suppress = is_eos & (nt_eff
                                 < jnp.take(min_len, cl))[:, None]
            logits = jnp.where(suppress, -1e30, logits)
            if full_logits:
                return caches, logits
            return caches, jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return flat_budget

    def _generate_beam(self, ids, last_x, caches, stk, e_arrays, h_arrays,
                       max_new_tokens, eos_token_id, k, length_penalty,
                       mesh_now, sk_flag, prompt):
        """Host drive for cache-backed beam search: jitted init (step 1)
        + compiled chunked beam scans; sequence reconstruction and final
        GNMT selection happen here by backtracking the recorded lineage.
        Selection semantics replicate _beam_search exactly (finished pool
        with strict-> admission, live beams length-penalized at the first
        all-finished step)."""
        b = ids.shape[0]
        eos = None if eos_token_id is None else int(eos_token_id)
        ikey = ("beam_init", k, eos, length_penalty, mesh_now)
        init = self._scan_cache.get(ikey)
        if init is None:
            init = self._build_beam_init(k, eos, length_penalty)
            self._scan_cache[ikey] = init
        ys0 = init(h_arrays, last_x)
        tok1, _, _, finished, scores, gen_len = ys0
        # beams share the prefill cache: replicate B -> B*K on the batch
        # axis (row b*K + j is beam j of batch row b)
        rep = lambda c: jnp.repeat(c, k, axis=2)            # noqa: E731
        caches = (tuple(rep(c) for c in caches)
                  if isinstance(caches, tuple) else rep(caches))
        hist = [tuple(np.asarray(a)[None] if a.ndim == 2 else
                      np.asarray(a) for a in ys0)]
        last_flat = tok1.reshape(-1)
        # the first generated token's KV is written when it is consumed
        # as the next step's INPUT at slot `prompt` (same convention as
        # the greedy drive) — prompt+1 here would leave slot `prompt`
        # all-zeros yet attendable and clamp the final write off the end
        t0 = prompt
        remaining = max_new_tokens - 1
        cap = int(os.environ.get("PADDLE_TPU_DECODE_CHUNK", "0")) or (
            8 if eos is not None else 64)
        # static shared-prefix split: largest power of two <= prompt
        # (bounded executable variants); below 64 the saving is noise
        split = 0
        if prompt >= 64:
            split = 1 << (int(prompt).bit_length() - 1)
        while remaining > 0:
            if eos is not None and bool(jnp.all(finished)):
                break
            chunk = cap
            while chunk > remaining:
                chunk //= 2
            key = ("beam", k, chunk, eos, length_penalty, mesh_now,
                   sk_flag, split)
            step = self._scan_cache.get(key)
            if step is None:
                step = self._build_beam_scan(k, chunk, eos,
                                             length_penalty, split)
                self._scan_cache[key] = step
            caches, last_flat, scores, finished, gen_len, ys = step(
                stk, e_arrays, h_arrays, caches, last_flat,
                jnp.asarray(t0, jnp.int32), scores, finished, gen_len)
            hist.append(tuple(np.asarray(a) for a in ys))
            t0 += chunk
            remaining -= chunk
        toks, bidx, fin_sc, fin_fl, sc_h, gl_h = (
            np.concatenate([h[i] for h in hist]) for i in range(6))
        T = toks.shape[0]
        all_fin = fin_fl.all(axis=(1, 2))
        t_stop = int(np.argmax(all_fin)) if all_fin.any() else T - 1

        def backtrack(t, row, beam):
            seq = np.empty(t + 1, np.int64)
            cur = beam
            for s in range(t, -1, -1):
                seq[s] = toks[s, row, cur]
                cur = bidx[s, row, cur]
            return seq

        norm = (sc_h[t_stop] /
                np.maximum(gl_h[t_stop], 1).astype(np.float32)
                ** length_penalty)
        ids_np = np.asarray(ids)
        out = np.empty((b, prompt + t_stop + 1), ids_np.dtype)
        out[:, :prompt] = ids_np
        for row in range(b):
            best = int(np.argmax(norm[row]))
            seq = backtrack(t_stop, row, best)
            if eos is not None:
                pool = fin_sc[:t_stop + 1, row]            # [T', K]
                if pool.max() > norm[row, best]:
                    t_f, k_f = np.unravel_index(int(np.argmax(pool)),
                                                pool.shape)
                    fin = backtrack(t_f, row, k_f)
                    seq = np.concatenate(
                        [fin, np.full(t_stop - t_f, eos, np.int64)])
            out[row, prompt:] = seq
        return Tensor(jnp.asarray(out))

    def _generate_spec(self, ids, caches, stk, e_arrays, h_arrays, first,
                       max_new_tokens, eos, do_sample, top_k, top_p,
                       temperature, min_length, repetition_penalty,
                       presence, k, prompt, mesh_now, sk_flag):
        """Host drive for speculative decoding over the compiled verify
        core: per-row NGramDrafter proposals -> ONE fixed-shape K+1
        verify step -> host acceptance (greedy exact-match / rejection
        sampling with the bonus-token resample) -> rollback as pure
        data. Rows accept independently, so per-row positions diverge —
        all bookkeeping is host vectors over the vector-t step, and the
        output is assembled with the chunked path's semantics (rows
        that finish early are eos-padded to the last finisher)."""
        from .spec_decode import (NGramDrafter, filtered_probs,
                                  greedy_accept, rejection_sample,
                                  truncate_emitted)
        b = ids.shape[0]
        rep_on = repetition_penalty != 1.0
        prompt_np = np.asarray(ids)
        first = np.asarray(first)
        rows = [[int(first[r])] for r in range(b)]
        drafters = []
        for r in range(b):
            d = NGramDrafter(k)
            d.reset(prompt_np[r])
            d.update(rows[r])
            drafters.append(d)
        lens = np.full(b, prompt, np.int32)
        nt = np.ones(b, np.int32)
        finished = ((first == eos) if eos is not None
                    else np.zeros(b, bool))
        eos_vec = jnp.full(b, -1 if eos is None else eos, jnp.int32)
        min_vec = jnp.full(b, int(min_length), jnp.int32)
        rp_vec = jnp.full(b, float(repetition_penalty), jnp.float32)
        vkey = ("verify", k, rep_on, do_sample, mesh_now, sk_flag)
        vstep = self._scan_cache.get(vkey)
        if vstep is None:
            vstep = jax.jit(
                self._build_verify_core(k, rep_on,
                                        greedy_out=not do_sample),
                donate_argnums=(3,))
            self._scan_cache[vkey] = vstep
        rng = None
        if do_sample:
            rng = np.random.RandomState(_host_seed(next_key()))
        while True:
            act = ~finished & (nt < max_new_tokens)
            if not act.any():
                break
            drafts = np.zeros((b, k), np.int32)
            dlen = np.zeros(b, np.int32)
            toks = np.zeros((b, k + 1), np.int32)
            for r in range(b):
                toks[r, 0] = rows[r][-1]
                if not act[r]:
                    continue
                d = drafters[r].propose()
                # never speculate past the row's remaining budget: the
                # bonus token always ships, so at most remaining-1
                # drafts are useful — this also keeps every landed
                # write < prompt + max_new_tokens <= Smax
                m = min(int(d.size), int(max_new_tokens - nt[r]) - 1)
                if m > 0:
                    drafts[r, :m] = d[:m]
                    dlen[r] = m
            toks[:, 1:] = drafts
            caches, out = vstep(
                stk, e_arrays, h_arrays, caches, jnp.asarray(toks),
                jnp.asarray(lens), jnp.asarray(dlen), jnp.asarray(act),
                jnp.asarray(nt), eos_vec, min_vec, rp_vec,
                presence if rep_on else jnp.zeros((b, 1), bool))
            # greedy steps return just the [B, K+1] argmax chain (the
            # only thing exact-match acceptance reads); sampling needs
            # the full logits for the rejection test
            out = (np.asarray(out).astype(np.float32) if do_sample
                   else np.asarray(out))
            new_rows, new_cols = [], []
            for r in range(b):
                if not act[r]:
                    continue
                m = int(dlen[r])
                if do_sample:
                    probs = filtered_probs(out[r, :m + 1], top_k, top_p,
                                           temperature)
                    kept, _ = rejection_sample(drafts[r, :m], probs, rng)
                else:
                    kept, _ = greedy_accept(drafts[r, :m],
                                            out[r, :m + 1])
                emitted, hit_eos = truncate_emitted(
                    kept, int(max_new_tokens - nt[r]), eos)
                nt[r] += len(emitted)
                rows[r].extend(emitted)
                lens[r] += len(emitted)
                if hit_eos:
                    finished[r] = True
                drafters[r].update(emitted)
                if rep_on:
                    new_rows.extend([r] * len(emitted))
                    new_cols.extend(emitted)
            if rep_on and new_rows:
                presence = presence.at[jnp.asarray(new_rows),
                                       jnp.asarray(new_cols)].set(True)
        width = max(len(t) for t in rows)
        pad = eos if eos is not None else 0
        out = np.full((b, prompt + width), pad, prompt_np.dtype)
        out[:, :prompt] = prompt_np
        for r in range(b):
            out[r, prompt:prompt + len(rows[r])] = rows[r]
        return Tensor(jnp.asarray(out))

    # --------------------------------------------------------------- drive
    @no_grad()
    def generate(self, input_ids, max_new_tokens=20, eos_token_id=None,
                 do_sample=False, top_k=0, top_p=1.0, temperature=1.0,
                 num_beams=1, length_penalty=1.0, min_length=0,
                 repetition_penalty=1.0, prefix_cache=None, spec_k=0):
        """Prefill the prompt via compiled chunked scans of the hidden
        core (LM head applied once at the end), then run the compiled
        chunked decode. Every device dispatch is a jitted scan; nothing
        runs eagerly here. num_beams > 1 runs beam search AGAINST the decode
        cache (see the beam builders above). min_length /
        repetition_penalty apply INSIDE the compiled steps via a [B, V]
        context-presence carry.

        prefix_cache: a ``paddle_tpu.inference.PrefixCache`` (the SAME
        object a ServingEngine may use). The longest published prefix of
        each row is block-copied into the fresh cache instead of being
        recomputed, prefill starts at the adopted offset, and the
        prompt's full blocks are committed back after prefill — repeated
        eval prompts skip their shared-prefix FLOPs across generate()
        calls too. Prefill starts at the MIN adopted length across rows
        (the chunked scan walks one scalar position for the whole
        batch); ignored under an active mesh (the pool carries no
        sharding annotations).

        spec_k: speculative decoding with the model-free n-gram drafter
        (spec_decode.py) and the compiled K+1-position verify step —
        pow-2 validated, 0 disables. Greedy outputs are token-identical
        to spec_k=0; composes with prefix_cache= (prefill is untouched).
        Batch eval loops with repetitive outputs (summarize/echo) emit
        several tokens per verify step."""
        from .spec_decode import validate_spec_k
        spec_k = validate_spec_k(spec_k)
        if spec_k and num_beams > 1:
            raise ValueError(
                "spec_k composes with greedy/sampling generation, not "
                "beam search (a draft has no beam lineage to verify)")
        if num_beams > 1 and do_sample:
            raise ValueError("beam search (num_beams>1) is deterministic; "
                             "do_sample=True is not supported with it")
        rep_on = repetition_penalty != 1.0
        pen_on = bool(min_length) or rep_on
        if pen_on and num_beams > 1:
            raise NotImplementedError(
                "min_length/repetition_penalty with beam search is not "
                "supported; use greedy/sampling generation")
        if rep_on:
            # only the repetition penalty needs the [B, V] presence mask
            # (and therefore a known vocab size); min_length alone works
            # with any head
            from ..nn.layer.common import Linear
            if type(self.head) is not Linear:
                raise NotImplementedError(
                    "repetition_penalty needs a Linear LM head (vocab "
                    "size must be known for the presence mask)")
        ids = input_ids._data if isinstance(input_ids, Tensor) else \
            jnp.asarray(np.asarray(input_ids))
        b, prompt = ids.shape
        assert prompt + max_new_tokens <= self.smax, (
            f"max_seq_len {self.smax} < prompt {prompt} + {max_new_tokens}")
        f = self.fmt
        f.eval()

        # ---- compiled prefill: chunked scans of the hidden core over the
        # prompt (pow-2 chunk ladder, same bounded-compile discipline as
        # decode), then ONE jitted head+sample on the final hidden state
        stk = self._stacked()
        e_arrays = [p._data for p in self._embed_params]
        h_arrays = self._maybe_quant_head(
            [p._data for p in self._head_params])
        toks_tm = jnp.swapaxes(ids.astype(jnp.int32), 0, 1)  # [S, B]
        mesh_now = self._mesh_mp()
        # the stacked-kernel escape hatch is trace-time state: it must be
        # part of every compiled-step cache key, or flipping it after a
        # compile failure would silently reuse the failing trace
        sk_flag = (os.environ.get("PADDLE_TPU_STACKED_KERNEL", "1")
                   + "/kw" + os.environ.get(
                       "PADDLE_TPU_KERNEL_CACHE_WRITE", "0"))
        pc = prefix_cache if mesh_now is None else None
        adopt_len, chains = 0, None
        ids_pc = (np.asarray(ids).astype(np.int32)
                  if pc is not None else None)
        if pc is not None and prompt > 1:
            ms = [pc.lookup(ids_pc[r]) for r in range(b)]
            # one scalar prefill position serves the whole batch, so the
            # adoptable length is the min across rows (b == 1 — the
            # repeated-eval-prompt case — loses nothing)
            n = min(len(mt) for mt in ms)
            if n:
                chains = [mt[:n] for mt in ms]
                adopt_len = n * pc.block_tokens
        if (os.environ.get("PADDLE_TPU_BULK_PREFILL", "0") == "1"
                and mesh_now is None and prompt > 1 and not adopt_len):
            # whole-prompt prefill: causal flash over [B, S], cache built
            # by padding the K/V scan output (see _build_bulk_prefill).
            # One executable per exact prompt length.
            # param dtype is part of the key: a weight swap to a new
            # dtype must rebuild (cache_dtype is baked at build time)
            pkey = ("bulkprefill", prompt, self._int8_cache(),
                    str(self.fmt.qkv_weights[0]._data.dtype))
            pstep = self._scan_cache.get(pkey)
            if pstep is None:
                pstep = self._build_bulk_prefill()
                self._scan_cache[pkey] = pstep
            last_x, caches = pstep(stk, e_arrays, ids.astype(jnp.int32))
            pos = prompt
        else:
            caches = self.init_cache(b)
            pos, last_x = 0, None
            if chains is not None:
                # splat the published prefix blocks into each row, then
                # start the chunked prefill at the adopted offset —
                # lookup() guarantees adopt_len <= prompt - 1, so the
                # loop below always runs and last_x is always produced
                for r, chain in enumerate(chains):
                    pc.store.acquire(chain)
                    try:
                        caches = pc.adopt(caches, r, chain)
                    finally:
                        pc.store.release(chain)
                pos = adopt_len
        while pos < prompt:
            chunk = 64
            while chunk > prompt - pos:
                chunk //= 2
            pkey = ("prefill", mesh_now, chunk, sk_flag)
            pstep = self._scan_cache.get(pkey)
            if pstep is None:
                pstep = self._build_prefill_scan(chunk)
                self._scan_cache[pkey] = pstep
            last_x, caches = pstep(stk, e_arrays, caches,
                                   toks_tm[pos:pos + chunk],
                                   jnp.asarray(pos, jnp.int32))
            pos += chunk
        if pc is not None and prompt >= pc.block_tokens:
            # commit-on-prefill, oneshot flavor: publish each row's full
            # blocks before decode touches (and donates) the cache buffer
            for r in range(b):
                pc.publish(caches, r, ids_pc[r])
        if num_beams > 1:
            return self._generate_beam(
                ids, last_x, caches, stk, e_arrays, h_arrays,
                max_new_tokens, eos_token_id, int(num_beams),
                float(length_penalty), mesh_now, sk_flag, prompt)
        eos_i = None if eos_token_id is None else int(eos_token_id)
        presence = None
        if rep_on:
            vocab = int(self._head_params[0].shape[1])
            presence = _presence_from(ids.astype(jnp.int32), vocab)
        # the head step bakes nt=0, so min_length enters as a BOOL (every
        # positive value compiles identically — avoid recompile churn)
        hkey = ("head", do_sample, top_k, top_p, temperature, mesh_now,
                eos_i if pen_on else None, bool(min_length),
                repetition_penalty)
        hstep = self._scan_cache.get(hkey)
        if hstep is None:
            hstep = self._build_head_sample(do_sample, top_k, top_p,
                                            temperature, eos_i,
                                            bool(min_length),
                                            repetition_penalty)
            self._scan_cache[hkey] = hstep
        hkey_rng = next_key() if do_sample else jax.random.PRNGKey(0)
        if pen_on:
            nxt = hstep(h_arrays, last_x, hkey_rng, presence)
            if rep_on:
                presence = presence.at[jnp.arange(b), nxt].set(True)
        else:
            nxt = hstep(h_arrays, last_x, hkey_rng)

        if spec_k:
            return self._generate_spec(
                ids, caches, stk, e_arrays, h_arrays, nxt,
                max_new_tokens, eos_i, do_sample, top_k, top_p,
                temperature, min_length, repetition_penalty, presence,
                spec_k, prompt, mesh_now, sk_flag)

        # ---- compiled decode: CHUNKED scan dispatch. Without eos, all
        # remaining tokens run in one device program; with eos, fixed-size
        # chunks with on-device finished-masking and a host early-exit
        # check between chunks. Cache key includes the active mesh
        # (entering/leaving an mp mesh must rebuild) and the chunk size.
        # host-side accumulation: ONE [chunk, B] device->host transfer per
        # chunk (not per token); only the last token stays on device as the
        # next dispatch's input
        host_parts = [np.asarray(nxt)[:, None]]
        last_tok = nxt
        finished = jnp.zeros((b,), bool)
        eos = None if eos_token_id is None else int(eos_token_id)
        remaining = max_new_tokens - 1
        if eos is not None:
            finished = finished | (nxt == eos)
            if bool(jnp.all(finished)):
                remaining = 0                 # everything ended at prefill
        # chunk sizes come from a power-of-two ladder so arbitrary
        # max_new_tokens values reuse a bounded set of compiled scan
        # variants (a fresh scan length would otherwise recompile inside
        # the generation loop). eos runs cap the chunk for early exit.
        chunk_env = int(os.environ.get("PADDLE_TPU_DECODE_CHUNK", "0"))
        cap = chunk_env or (8 if eos is not None else 64)
        t0 = prompt
        while remaining > 0:
            chunk = cap
            while chunk > remaining:
                chunk //= 2
            key = (do_sample, top_k, top_p, temperature,
                   self._mesh_mp(), chunk, eos, sk_flag,
                   min_length, repetition_penalty)
            step = self._scan_cache.get(key)
            if step is None:
                step = self._build_scan_step(*key[:4], chunk, eos,
                                             min_length,
                                             repetition_penalty)
                self._scan_cache[key] = step
            # one split per chunk: per-token subkeys ride the scan xs
            base = next_key() if do_sample else jax.random.PRNGKey(0)
            keys = jax.random.split(base, chunk)
            if rep_on:
                ck, caches, finished, presence = step(
                    stk, e_arrays, h_arrays, caches, last_tok,
                    jnp.asarray(t0, jnp.int32), keys, finished,
                    presence,
                    jnp.asarray(t0 - prompt + 1, jnp.int32))
            elif pen_on:
                ck, caches, finished = step(
                    stk, e_arrays, h_arrays, caches, last_tok,
                    jnp.asarray(t0, jnp.int32), keys, finished, None,
                    jnp.asarray(t0 - prompt + 1, jnp.int32))
            else:
                ck, caches, finished = step(
                    stk, e_arrays, h_arrays, caches, last_tok,
                    jnp.asarray(t0, jnp.int32), keys, finished)
            host_parts.append(np.asarray(ck).T)        # [B, chunk]
            last_tok = ck[-1]
            t0 += chunk
            remaining -= chunk
            if eos is not None and bool(jnp.all(finished)):
                break
        out = np.concatenate([np.asarray(ids)] + host_parts, axis=1)
        if eos is not None and bool(jnp.all(finished)):
            # per-token early-stop semantics (matches generate()): the
            # output ends at the step where the LAST row emitted its first
            # eos; any later all-eos padding the chunk produced is trimmed
            gen = out[:, prompt:]
            first_eos = np.argmax(gen == eos, axis=1)   # rows all have one
            out = out[:, : prompt + int(first_eos.max()) + 1]
        return Tensor(out)


def generate_fused(fmt, input_ids, embed, head, max_new_tokens=20,
                   max_seq_len=None, eos_token_id=None, do_sample=False,
                   top_k=0, top_p=1.0, temperature=1.0, use_rotary=False,
                   num_beams=1, length_penalty=1.0, min_length=0,
                   repetition_penalty=1.0, prefix_cache=None, spec_k=0):
    """One-shot driver over FusedDecoder (see class docstring)."""
    ids = input_ids._data if isinstance(input_ids, Tensor) else \
        jnp.asarray(np.asarray(input_ids))
    smax = max_seq_len or ids.shape[1] + max_new_tokens
    dec = FusedDecoder(fmt, embed, head, smax, use_rotary=use_rotary)
    return dec.generate(input_ids, max_new_tokens, eos_token_id, do_sample,
                        top_k, top_p, temperature, num_beams=num_beams,
                        length_penalty=length_penalty,
                        min_length=min_length,
                        repetition_penalty=repetition_penalty,
                        prefix_cache=prefix_cache, spec_k=spec_k)
