"""chip_smoke.py — the quickest proof that the system starts on the chip.

One process, no arguments: drives the two main paths ONCE through the entry
points a user calls, at GPT-2-124M width on one TPU chip, and checks what
comes out by the repo's own means.

  * Trainer — ``paddle_tpu.models.gpt.gpt2_124m()`` in bf16, AdamW with fp32
    master weights, the step wrapped by ``paddle.jit.to_static``, batch
    8 x 1024 on one fixed batch from a seed. Losses finite, the first within
    5% of ln(vocab), the last below the first; the flash-attention Pallas
    kernel compiled into the step (``tpu_custom_call`` in the lowered text).
    The state is donated (``to_static``'s default); then one more step built
    with ``donate_state=False``, the opt-out.
  * Server — ``FusedMultiTransformer(768, 12, 3072, 12 layers)`` + embedding
    + head in bf16 behind ``ServingEngine`` (8 slots, 1024 positions, paged
    KV) behind ``Gateway(Router([LocalReplica]))``: completions POSTed over
    the real socket. Full token budgets, KV block conservation, a prefix
    hit, zero retraces after warm-up, the paged Pallas kernel as the
    RECORDED attention path, and a comparison that can fail: the same
    prompts through a second engine on the XLA gather path
    (``PADDLE_TPU_STACKED_KERNEL=0``) give last-position logits within bf16
    tolerance. Then the ``kv_quant="int8"`` and ``weight_quant="int4"``
    engines start and decode.

``--chips 4`` runs ONLY the four-chip phase and what it is compared with,
in one process that drives all four chips: Fleet hybrid training
(mp 2 x sharding 2, ZeRO-3) of a LLaMA-shaped model against the same-seed
one-device run, and the ``init_serving_mesh(4)`` engine against the
one-device engine, each with a placement proof from ``addressable_shards``.

It fails (non-zero exit, traceback) unless JAX reports a TPU; it never sets
the platform itself. Any phase that raises or fails a check ends the run
non-zero. The LAST stdout line of a run that passed is
``{"ok": true, "device": {"platform", "kind", "count"}}``. Every time
printed on the way is a smoke timing, not a benchmark.
"""
from __future__ import annotations

import argparse
import collections
import http.client
import json
import math
import os
import re
import threading
import time
from unittest import mock

import numpy as np

SEED = 0

# the gpt2_124m trainer cell (GPT-2 124M at its published widths)
TRAIN = {"build": "gpt2_124m", "batch": 8, "seq": 1024, "steps": 8,
         "lr": 3e-4}

# the GPT-2-124M-width serving stack.
# Prompt lengths are in tokens; "shared" is the prefix two requests share
# (>= 2 KV blocks of the default 64 tokens).
SERVE = {"hidden": 768, "heads": 12, "ffn": 3072, "layers": 12,
         "vocab": 50304, "slots": 8, "smax": 1024, "new_tokens": 32,
         "prefix_blocks": 32, "shared": 160,
         "warm": ((160, 20), (160, 40), (0, 16)),
         "measured": ((160, 40), (160, 70), (0, 300), (0, 16), (0, 96),
                      (0, 48)),
         "quant_prompts": (40, 100), "quant_new_tokens": 8}

# the LLaMA-shaped hybrid-training cell (hidden 1024, FFN 2816)
HYBRID = {"hidden": 1024, "layers": 16, "heads": 16, "ffn": 2816,
          "vocab": 32000, "batch": 8, "seq": 1024, "steps": 3, "lr": 1e-4}

# bf16 tolerances of the comparisons that can fail
LOGIT_RTOL = 0.05       # max |kernel - xla| over max |xla|, last position
LOSS_RTOL = 0.02        # sharded vs one-device loss, per step

_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")


def say(msg):
    print(f"chip_smoke: {msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {msg}")


# ------------------------------------------------------------------ device
def _require_chips(n):
    """The first device, if JAX reports ``n`` TPU chips; raises otherwise
    (non-zero exit, no result line). Never sets the platform."""
    import jax
    from paddle_tpu.device.chip import require_tpu
    dev = require_tpu()
    check(len(jax.devices()) == n,
          f"needs {n} TPU chip(s), JAX reports {len(jax.devices())}")
    return dev


def _check_mosaic(what, lowered_text=None):
    """The Pallas path was COMPILED for the chip: not interpret mode, and
    (given a step's lowered text) the kernel is in the program."""
    import paddle_tpu.ops.pallas as pallas
    check(not pallas._interpret(), f"{what}: Pallas is in interpret mode")
    if lowered_text is not None:
        n = lowered_text.count("tpu_custom_call")
        check(n > 0, f"{what}: no tpu_custom_call in the lowered step")
        say(f"{what}: {n} tpu_custom_call(s) in the lowered step")


def _environment(dev, cache_dir):
    import importlib.metadata as md

    import jax
    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"environment: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}; device_kind {dev.device_kind!r} x "
        f"{len(jax.devices())}; compile cache {cache_dir} "
        f"({warm} entries at start)")


def _end_phase(name):
    """Report the phase's peak device memory, then free what it left (each
    phase rebuilds its state from the seed)."""
    import jax
    from paddle_tpu.device.chip import release_device_memory
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(f"dev{d.id} {stats['peak_bytes_in_use'] / 2**30:.2f}"
                         " GiB")
    n = release_device_memory()
    say(f"[{name}] done; peak_bytes_in_use (process so far): "
        f"{', '.join(peaks) or 'not reported'}; released {n} device arrays")


def _spy(module, name):
    """Count calls of ``module.name`` by module attribute (callers look the
    kernel up through the module at trace time): ``.call_count``."""
    return mock.patch.object(module, name, wraps=getattr(module, name))


# ----------------------------------------------------------------- trainer
def _train_losses(model, opt, x, y, steps, label, after_first=None):
    """``steps`` compiled steps on the one fixed batch; ``after_first``
    (the sharded run's placement) maps (x, y) once the first call has
    created the optimizer slots. Returns (losses, per-call seconds, the
    compiled step, its last arguments, the eager step function)."""
    import jax
    import paddle_tpu as paddle

    def _step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(_step)
    losses, secs = [], []
    for i in range(steps):
        if i == 1 and after_first is not None:
            x, y = after_first(x, y)
        t0 = time.perf_counter()
        loss = step(x, y)
        jax.block_until_ready(loss._data)
        secs.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(loss._data, np.float32)))
        say(f"[{label}] step {i + 1}: loss {losses[-1]:.4f}  "
            f"({secs[-1]:.2f} s)")
        check(math.isfinite(losses[-1]), f"{label}: loss not finite")
    return losses, secs, step, (x, y), _step


def phase_trainer():
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt
    from paddle_tpu.ops.pallas import flash_attention as fa

    c = TRAIN
    paddle.seed(SEED)
    model = getattr(gpt, c["build"])()
    model.bfloat16()            # bf16 params; fp32 master weights in AdamW
    opt = paddle.optimizer.AdamW(learning_rate=c["lr"],
                                 parameters=model.parameters(),
                                 multi_precision=True)
    vocab = model.config.vocab_size
    n_params = sum(p.size for p in model.parameters())
    ids = np.random.RandomState(SEED).randint(
        0, vocab, (c["batch"], c["seq"] + 1)).astype(np.int32)
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
    say(f"[trainer] {c['build']} bf16, {n_params / 1e6:.1f}M params, "
        f"AdamW(multi_precision), batch {c['batch']} x {c['seq']}")

    with _spy(fa, "flash_attention") as spy:
        losses, secs, step, _, eager = _train_losses(
            model, opt, x, y, c["steps"], "trainer")
    check(abs(losses[0] - math.log(vocab)) <= 0.05 * math.log(vocab),
          f"first loss {losses[0]:.3f} not within 5% of ln(vocab) "
          f"{math.log(vocab):.3f}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    check(spy.call_count > 0, "flash_attention was never dispatched (the XLA "
          "composite in nn/functional/attention.py ran instead)")
    _check_mosaic("trainer", step.lower(x, y).as_text())
    last = paddle.jit.call_timeline()[-1]
    check(last["donated"] > 0, "the steady step donated no state leaf: "
          "to_static's default donates them")
    say(f"[trainer] flash_attention traced {spy.call_count}x; compile+first "
        f"run {secs[0]:.1f} s (slot-creation trace) + {secs[1]:.1f} s "
        f"(steady signature); steady step "
        f"{1e3 * float(np.median(secs[2:])):.1f} ms median of "
        f"{len(secs) - 2} (smoke timing, not a benchmark); state leaves "
        f"donated {last['donated']}, kept {last['kept']}")

    # to_static donates by default; does the opt-out still start?
    undonated = paddle.jit.to_static(eager, donate_state=False)
    t0 = time.perf_counter()
    loss = undonated(x, y)
    jax.block_until_ready(loss._data)
    val = float(np.asarray(loss._data, np.float32))
    check(math.isfinite(val), "undonated step: loss not finite")
    last = paddle.jit.call_timeline()[-1]
    say(f"[trainer] undonated step (donate_state=False) ran: yes, loss "
        f"{val:.4f}, donated {last['donated']}, kept {last['kept']} "
        f"({time.perf_counter() - t0:.1f} s with its compile)")


# ------------------------------------------------------------------ server
def _serving_model():
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import FusedMultiTransformer
    from paddle_tpu.nn.layer.common import Embedding, Linear

    c = SERVE
    paddle.seed(SEED)
    embed = Embedding(c["vocab"], c["hidden"])
    fmt = FusedMultiTransformer(c["hidden"], c["heads"], c["ffn"],
                                num_layers=c["layers"],
                                normalize_before=True)
    head = Linear(c["hidden"], c["vocab"], bias_attr=False)
    for lay in (embed, fmt, head):
        lay.bfloat16()
    fmt.eval()
    return fmt, embed, head


def _engine(model, **kw):
    from paddle_tpu.inference.serving import ServingEngine
    fmt, embed, head = model
    return ServingEngine(fmt, embed, head, num_slots=SERVE["slots"],
                         max_seq_len=SERVE["smax"], **kw)


def _prompts(plan, rng, shared=np.zeros(0, np.int64)):
    """[(shared-prefix tokens, own tokens)] -> token-id lists."""
    return [[int(t) for t in np.concatenate(
        [shared[:n_shared], rng.randint(1, SERVE["vocab"], (n_own,))])]
        for n_shared, n_own in plan]


def _post(port, prompt, stream=False):
    """One /v1/completions over the real socket -> (status, tokens)."""
    body = {"prompt": prompt, "max_tokens": SERVE["new_tokens"]}
    if stream:
        body["stream"] = True
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    conn.request("POST", "/v1/completions", body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    if resp.status != 200:
        return resp.status, data.decode(errors="replace")
    if not stream:
        return 200, json.loads(data)["choices"][0]["tokens"]
    lines = [ln[6:] for ln in data.split(b"\n") if ln.startswith(b"data: ")]
    check(lines and lines[-1].strip() == b"[DONE]",
          "SSE stream does not end with data: [DONE]")
    tokens = []
    for ln in lines[:-1]:
        tokens += json.loads(ln)["choices"][0]["tokens"]
    return 200, tokens


def _post_all(port, prompts, streamed=()):
    """POST every prompt concurrently; [(status, tokens)] in order."""
    out = [None] * len(prompts)

    def one(i):
        try:
            out[i] = _post(port, prompts[i], stream=i in streamed)
        except Exception as e:      # surfaces as a failed check below
            out[i] = (-1, repr(e))
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def _direct(eng, prompts, new_tokens):
    """The same prompts through the engine API; token lists in order."""
    rids = [eng.submit(np.asarray(p, np.int32), max_new_tokens=new_tokens)
            for p in prompts]
    eng.run()
    return [[int(t) for t in eng.results[r]["tokens"]] for r in rids]


def _teacher_forced_logits(eng, seqs, n_last):
    """For each token sequence, the logits at its last ``n_last`` positions
    ([n_last, V] each; entry k is what the token AFTER position
    len - n_last + k is the argmax of) through THIS engine's compiled block
    core: the sequence goes in as C-column chunks, written to and attended
    through a copy of the paged pool. The engine API returns tokens only,
    so the comparisons read logits from the core the budget step runs,
    built with ``full_logits``. Also returns the jitted core and its last
    arguments (for the four-chip phase's compiled-HLO listing)."""
    import jax
    import jax.numpy as jnp
    dec, b, c = eng.dec, eng.num_slots, eng._budget_cols
    nblk = eng.smax // eng.prefill_cap
    check(eng.pool.num_blocks >= b * nblk,
          "logit probe needs a full block table per row")
    tables = np.arange(b * nblk, dtype=np.int32).reshape(b, nblk)
    core = jax.jit(dec._build_budget_core(c, full_logits=True, chain=True))
    stk = dec._stacked()
    e_arrays = [p._data for p in dec._embed_params]
    h_arrays = dec._maybe_quant_head([p._data for p in dec._head_params])
    zero = jnp.zeros(b, jnp.int32)
    fixed = (jnp.full(b, c, jnp.int32), zero, jnp.ones(b, jnp.int32),
             jnp.full(b, -1, jnp.int32), zero, jnp.ones(b, jnp.float32),
             eng._presence_arg(), zero)
    out = []
    for g in range(0, len(seqs), b):            # b rows at a time
        rows = seqs[g:g + b]
        caches = dict(eng._caches, tbl=jnp.asarray(tables))
        lens = np.zeros(b, np.int32)
        got = [[] for _ in rows]
        for i in range(-(-max(len(p) for p in rows) // c)):
            toks = np.zeros((b, c), np.int32)
            seg = np.zeros(b, np.int32)
            for r, p in enumerate(rows):
                part = p[i * c:(i + 1) * c]
                toks[r, :len(part)] = part
                seg[r] = len(part)
            args = (stk, e_arrays, h_arrays, caches, jnp.asarray(toks),
                    jnp.asarray(lens), jnp.asarray(seg)) + fixed
            caches, logits = core(*args)
            for r, p in enumerate(rows):
                lo = max(len(p) - n_last, i * c)    # wanted columns here
                if lo < i * c + seg[r]:
                    got[r].append(np.asarray(
                        logits[r, lo - i * c:seg[r]], np.float32))
            lens = lens + seg
        out += [np.concatenate(x) for x in got]
    return out, core, args


def _compare_paths(ta, tb, la, lb, label_a, label_b, tag):
    """The comparison that can fail. ``la``/``lb`` are the two paths'
    teacher-forced logits over path A's own greedy stream (prompt +
    ``ta``), ``tb`` path B's greedy tokens.

      1. the logits agree within bf16 tolerance at every generated
         position;
      2. every token path A served is the argmax of A's probed logits
         there, or a near-tie with it (ties the probe to what the engine
         really emitted);
      3. where the two greedy streams first diverge — the contexts are
         still equal there — the two choices are a near-tie in B's
         logits. A divergence is reported, and allowed, only as that.

    A near-tie is a gap of at most 3x the two paths' measured
    disagreement (2x from the argument, the rest for the block shapes in
    which the engines' own runs differ from the probe's)."""
    check(all(np.isfinite(a).all() for a in la + lb), "logits not finite")
    err = max(float(np.abs(a - b).max()) for a, b in zip(la, lb))
    scale = max(float(np.abs(b).max()) for b in lb)
    say(f"[{tag}] teacher-forced logits {label_a} vs {label_b} over "
        f"{sum(len(a) for a in la)} generated positions: max |diff| "
        f"{err:.4g} over max |logit| {scale:.4g} = {err / scale:.4f} "
        f"(bound {LOGIT_RTOL})")
    check(err <= LOGIT_RTOL * scale,
          f"{label_a} and {label_b} logits differ by {err / scale:.4f} of "
          f"scale > {LOGIT_RTOL}")
    short = max(float(la[r][j].max() - la[r][j][t])
                for r, a in enumerate(ta) for j, t in enumerate(a))
    say(f"[{tag}] served tokens vs the probe's argmax ({label_a}): "
        f"largest shortfall {short:.4g} (near-tie bound {3 * err:.4g})")
    check(short <= 3 * err,
          f"{label_a} served a token {short:.4g} below its probed argmax")
    same = 0
    for r, (a, b) in enumerate(zip(ta, tb)):
        first = next((i for i, (u, v) in enumerate(zip(a, b)) if u != v),
                     None)
        same += first is None
        if first is not None:
            gap = abs(float(lb[r][first][b[first]] - lb[r][first][a[first]]))
            say(f"[{tag}] request {r}: greedy tokens first diverge at "
                f"token {first} of {len(a)}; the two choices are "
                f"{gap:.4g} apart in {label_b}'s logits")
            check(gap <= 3 * err,
                  f"request {r}: streams diverge at token {first} although "
                  f"the choices are {gap:.4g} apart (> {3 * err:.4g})")
    say(f"[{tag}] greedy tokens {label_a} vs {label_b}: {same} of "
        f"{len(ta)} requests identical to the end"
        + ("" if same == len(ta) else "; every divergence is a near-tie"))


def _forced(prompts, tokens):
    """prompt + its greedy stream but the last token: the positions that
    predict every generated token."""
    return [p + t[:-1] for p, t in zip(prompts, tokens)]


def _serve_over_http(eng, warm, measured):
    """Warm-up then measured completions through Gateway -> Router ->
    LocalReplica over the real socket. Returns the measured token lists."""
    from paddle_tpu.serving_cluster import Gateway, LocalReplica, Router
    rep = LocalReplica("replica0", eng)
    # the first dispatch of each core compiles for tens of seconds inside
    # engine.step(): the heartbeat must outlast it
    gw = Gateway(Router([rep], hb_dead_s=900.0), port=0).start_background()
    try:
        t0 = time.perf_counter()
        res = _post_all(gw.port, warm[:1]) + _post_all(gw.port, warm[1:])
        check(all(st == 200 for st, _ in res), f"warm-up statuses {res}")
        say(f"[server] {len(warm)} warm-up completions over HTTP in "
            f"{time.perf_counter() - t0:.1f} s (compiles included)")
        traces0 = eng._traces_total()
        t0 = time.perf_counter()
        # the first prefix-sharing request finishes (and publishes its
        # blocks) before its twin arrives with the rest, all at once
        res = _post_all(gw.port, measured[:1])
        res += _post_all(gw.port, measured[1:],
                         streamed={len(measured) - 2})
        dt = time.perf_counter() - t0
        for r, (st, toks) in enumerate(res):
            check(st == 200, f"request {r}: HTTP {st} {toks}")
            check(len(toks) == SERVE["new_tokens"],
                  f"request {r}: {len(toks)} tokens, budget "
                  f"{SERVE['new_tokens']}")
        retraces = eng._traces_total() - traces0
        say(f"[server] {len(measured)} completions (1 streamed) over HTTP: "
            f"all 200, {SERVE['new_tokens']} tokens each, in {dt:.2f} s "
            f"(smoke timing, not a benchmark); retraces after warm-up: "
            f"{retraces}")
        check(retraces == 0, f"{retraces} retraces after warm-up")
    finally:
        gw.stop()
        rep.close()
    return [toks for _, toks in res]


def _check_engine_books(eng):
    m = eng.metrics()
    check(m["kv_blocks_used"] + m["kv_blocks_free"] == m["kv_blocks_total"],
          f"KV blocks leak: used {m['kv_blocks_used']} + free "
          f"{m['kv_blocks_free']} != total {m['kv_blocks_total']}")
    check(m["prefix_hits"] >= 1, f"no prefix hit (hits {m['prefix_hits']})")
    say(f"[server] kv blocks used {m['kv_blocks_used']} + free "
        f"{m['kv_blocks_free']} == total {m['kv_blocks_total']}; prefix "
        f"hits {m['prefix_hits']}, prefill tokens saved "
        f"{m['prefill_tokens_saved']}")


def _paths(eng):
    return eng.telemetry_snapshot()["weights"]["step_paths"]


def phase_server():
    import paddle_tpu.ops.pallas.decode_attention as da

    c = SERVE
    model = _serving_model()
    rng = np.random.RandomState(SEED + 1)
    warm = _prompts(c["warm"], rng,
                    rng.randint(1, c["vocab"], (c["shared"],)))
    measured = _prompts(c["measured"], rng,
                        rng.randint(1, c["vocab"], (c["shared"],)))
    say(f"[server] FusedMultiTransformer({c['hidden']}, {c['heads']}, "
        f"{c['ffn']}, {c['layers']} layers) + Embedding({c['vocab']}) + "
        f"head, bf16; {c['slots']} slots x {c['smax']} positions, paged "
        f"KV; prompts {sorted(len(p) for p in measured)} tokens")

    with _spy(da, "decode_attention_paged") as spy:
        eng = _engine(model, prefix_cache_blocks=c["prefix_blocks"])
        http_tokens = _serve_over_http(eng, warm, measured)
        _check_engine_books(eng)
        check(spy.call_count > 0, "decode_attention_paged never dispatched")
        check(_paths(eng) == "attn=paged_pallas",
              f"kernel engine took {_paths(eng)!r}, not the paged kernel")
        _check_mosaic("server")
        say(f"[server] engine A attention path (recorded): {_paths(eng)}; "
            f"decode_attention_paged traced {spy.call_count}x")
        n = c["new_tokens"]
        seqs = _forced(measured, http_tokens)
        la, _, _ = _teacher_forced_logits(eng, seqs, n)
        kernel_calls = spy.call_count
        del eng

        # the comparison engine: PADDLE_TPU_STACKED_KERNEL=0 routes
        # attention to the XLA gather path. The step cores read the
        # variable while they TRACE, so it stays set until this engine's
        # cores (and the probe's) have compiled
        with mock.patch.dict(os.environ, PADDLE_TPU_STACKED_KERNEL="0"):
            eng_x = _engine(model)
            xla_tokens = _direct(eng_x, measured, n)
            lx, _, _ = _teacher_forced_logits(eng_x, seqs, n)
        check(spy.call_count == kernel_calls and
              _paths(eng_x) == "attn=paged_xla_gather",
              f"comparison engine took {_paths(eng_x)!r}, not the XLA "
              "gather path")
        say(f"[server] engine B attention path (recorded): {_paths(eng_x)}")
        del eng_x
    _compare_paths(http_tokens, xla_tokens, la, lx,
                   "paged Pallas kernel (HTTP)", "XLA gather", "server")

    # shipped constructor arguments must start on the chip
    qp = _prompts([(0, n) for n in c["quant_prompts"]], rng)
    for label, kw, want in (
            ("kv_quant='int8' (flat budget, prefill_cap 32)",
             dict(kv_quant="int8", flat_budget=True, prefill_cap=32),
             ("attn=paged_flat_i8_pallas", "attn=paged_i8_pallas")),
            ("weight_quant='int4'", dict(weight_quant="int4"),
             ("attn=paged_pallas", "mm=int4_fused_pallas"))):
        eng_q = _engine(model, **kw)
        toks = _direct(eng_q, qp, c["quant_new_tokens"])
        check(all(len(t) == c["quant_new_tokens"] for t in toks),
              f"{label}: token counts {[len(t) for t in toks]}")
        check(_paths(eng_q).split(",") == sorted(want),
              f"{label}: took {_paths(eng_q)!r}, expected {sorted(want)}")
        say(f"[server] {label} engine started and decoded "
            f"{c['quant_new_tokens']} tokens x {len(qp)}; paths "
            f"{_paths(eng_q)}")
        del eng_q


# --------------------------------------------------------------- four chips
def _bytes_per_device(arrays):
    per = collections.Counter()
    for a in arrays:
        for sh in a.addressable_shards:
            per[sh.device.id] += int(sh.data.nbytes)
    return dict(sorted(per.items()))


def _placement_proof(label, arrays, n_dev):
    per = _bytes_per_device(arrays)
    total = sum(per.values())
    say(f"[{label}] bytes on each device: "
        + ", ".join(f"dev{d} {b / 2**20:.1f} MiB" for d, b in per.items()))
    check(len(per) == n_dev and min(per.values()) > 0
          and max(per.values()) < 0.5 * total,
          f"{label}: not spread over {n_dev} devices: {per}")
    return per


def _collectives(label, compiled_text):
    found = collections.Counter(_COLLECTIVE.findall(compiled_text))
    say(f"[{label}] collectives in the compiled HLO: "
        f"{dict(found) or 'none'}")
    return found


def _llama():
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    c = HYBRID
    paddle.seed(SEED)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=c["vocab"], hidden_size=c["hidden"],
        num_layers=c["layers"], num_heads=c["heads"],
        intermediate_size=c["ffn"], max_position=c["seq"],
        tensor_parallel=True))
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=c["lr"],
                                 parameters=model.parameters(),
                                 multi_precision=True)
    ids = np.random.RandomState(SEED).randint(
        0, c["vocab"], (c["batch"], c["seq"] + 1)).astype(np.int32)
    return model, opt, ids


def hybrid_reference():
    """The same-seed run on ONE device (no mesh): the losses the sharded
    run is held to."""
    import paddle_tpu as paddle
    model, opt, ids = _llama()
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
    return _train_losses(model, opt, x, y, HYBRID["steps"],
                         "hybrid one-device")[0]


def hybrid_sharded(ref_losses, n_dev):
    """fleet.init(dp 1, mp 2, sharding 2) + group_sharded_parallel
    ("p_g_os", ZeRO-3): the north-star training path over the chips."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.parallel import apply_shardings, shard_batch

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    model, opt, ids = _llama()
    model, opt, _ = group_sharded_parallel(model, opt, level="p_g_os")
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])

    def place(x, y):
        # the first call created the optimizer slots; now place every
        # parameter and slot per its spec and shard the batch
        say(f"[hybrid] apply_shardings placed {apply_shardings()} "
            "sharded tensors")
        return shard_batch(x), shard_batch(y)

    losses, _, step, (x, y), _ = _train_losses(
        model, opt, x, y, HYBRID["steps"], "hybrid mp2 x sharding2",
        after_first=place)
    for i, (got, ref) in enumerate(zip(losses, ref_losses)):
        check(abs(got - ref) <= LOSS_RTOL * abs(ref),
              f"step {i + 1}: sharded loss {got:.4f} vs one-device "
              f"{ref:.4f} beyond {LOSS_RTOL}")
    say(f"[hybrid] losses {[round(v, 4) for v in losses]} vs one-device "
        f"{[round(v, 4) for v in ref_losses]}: within {LOSS_RTOL} per step")
    from paddle_tpu.tensor.tensor import persistent_tensors
    state = [t._data for t in persistent_tensors()
             if hasattr(t._data, "addressable_shards") and t._data.ndim > 0]
    _placement_proof("hybrid: parameters + optimizer state", state, n_dev)
    found = _collectives("hybrid", step.lower(x, y).compile().as_text())
    check(sum(found.values()) > 0, "hybrid step compiled no collective")


def mesh_serving(n_dev):
    """init_serving_mesh(n) + the Server engine, held to the one-device
    engine (logits within bf16 tolerance over the whole greedy stream,
    tokens equal up to near-ties), weights and KV pool spread over the
    chips, and the residency identity of ROADMAP.md S7."""
    from paddle_tpu.parallel import init_serving_mesh
    c = SERVE
    model = _serving_model()
    rng = np.random.RandomState(SEED + 1)
    prompts = _prompts(c["measured"], rng,
                       rng.randint(1, c["vocab"], (c["shared"],)))
    n = c["new_tokens"]
    eng1 = _engine(model)
    ref = _direct(eng1, prompts, n)
    seqs = _forced(prompts, ref)
    l1, _, _ = _teacher_forced_logits(eng1, seqs, n)
    dense_w = sum(int(a.nbytes) for a in eng1._weight_arrays())
    dense_pool = sum(int(a.nbytes) for a in eng1._caches.values())
    say(f"[mesh serving] one-device engine: paths {_paths(eng1)}; weights "
        f"{dense_w / 2**20:.1f} MiB, KV pool {dense_pool / 2**20:.1f} MiB")
    del eng1

    init_serving_mesh(n_dev, num_heads=c["heads"], ffn_dim=c["ffn"])
    eng = _engine(model)
    got = _direct(eng, prompts, n)
    say(f"[mesh serving] mp={n_dev} engine: paths {_paths(eng)}")
    check(_paths(eng) == "attn=paged_pallas_shard_map",
          f"sharded engine took {_paths(eng)!r}")
    ln, core, args = _teacher_forced_logits(eng, seqs, n)
    # bf16 partial sums meet in a different order across chips, so the
    # streams may part at a near-tie; anything wider fails
    _compare_paths(ref, got, l1, ln, "one device", f"mp={n_dev}",
                   "mesh serving")
    _placement_proof("mesh serving: weights + KV pool",
                     list(eng._weight_arrays())
                     + list(eng._caches.values()), n_dev)
    m = eng.metrics()
    per_dev, repl = m["weight_bytes_per_device"], m["weight_bytes_replicated"]
    check(m["weight_shard_count"] == n_dev
          and (per_dev - repl) * n_dev + repl == dense_w,
          f"weight residency identity broke: ({per_dev} - {repl}) x "
          f"{n_dev} + {repl} != {dense_w}")
    check(m["kv_shard_count"] == n_dev
          and m["kv_shard_pool_bytes"] * n_dev == dense_pool,
          f"pool residency broke: {m['kv_shard_pool_bytes']} x {n_dev} "
          f"!= {dense_pool}")
    say(f"[mesh serving] residency identity holds: weights ({per_dev} - "
        f"{repl}) x {n_dev} + {repl} == {dense_w} B; pool "
        f"{m['kv_shard_pool_bytes']} x {n_dev} == {dense_pool} B")
    _collectives("mesh serving", core.lower(*args).compile().as_text())


def phase_four_chips(n_dev=4):
    # the one-device references run first, while no mesh is active
    ref_losses = hybrid_reference()
    _end_phase("hybrid one-device")
    mesh_serving(n_dev)
    _end_phase("mesh serving")
    hybrid_sharded(ref_losses, n_dev)
    _end_phase("hybrid sharded")


# -------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase and what it is "
                         "compared with")
    args = ap.parse_args(argv)

    from paddle_tpu.device.chip import device_record, use_compile_cache
    dev = _require_chips(args.chips)
    _environment(dev, use_compile_cache())
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips()
    else:
        phase_trainer()
        _end_phase("trainer")
        phase_server()
        _end_phase("server")
    say(f"all phases passed in {time.perf_counter() - t0:.0f} s")
    print(json.dumps({"ok": True, "device": device_record(dev)}),
          flush=True)


if __name__ == "__main__":
    main()
