"""Runner ``serve``: a model family behind ``ServingEngine`` behind
``Gateway(Router([LocalReplica]))`` on a real socket, under an open-loop
schedule sent by ``loadgen.py`` from a process of its own.

Construction and the HTTP helpers follow ``chip_smoke.py`` (PR 21's
chip-proven path): ``_serving_model``/``_engine``/``_serve_over_http``/
``_post``/``_teacher_forced_logits``. What is new is the steady window, the
client-side clock, and the float32 reference.

Set-up (all of it inside ``setup_s``): weights from the seed, engine,
gateway, warm-up completions that touch every compiled shape the window
uses, the comparison with the reference, then the schedule's ramp. The
window opens ``ramp_s`` after the schedule starts, on a running system.
"""
from __future__ import annotations

import http.client
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from benchmark import harness, latency, schedule, trace_reduce

# The comparison's requests double as the warm-up: CHECK_REQUESTS requests at
# the stratified quantiles of the cell's own length distributions (the same
# sizes for every seed, so set-up is the same work), the first alone, so that
# the budget core runs a lone prefill and then the plain decode chunk, the
# rest at once, so that admission joins a running batch. PR 21's three
# warm-up completions left 0 retraces after them; the window checks again.
# Teacher-forced logits of the engine's compiled core (bf16 weights, bf16
# activations, float32 softmax and LayerNorm statistics) against the float32
# reference, as max |diff| over max |reference logit|. bf16 keeps 8 bits, so
# one rounding is 2**-9 = 0.002 of a value; PR 21 measured 0.008-0.010
# between two bf16 paths of this stack at 12 layers. 0.05 leaves room for
# the depth of 36 and still fails a path that computes in less than bf16:
# per-row absmax int8 (7 bits, on K and V or on the weights) alone puts a
# rounding of 0.004-0.008 on every product.
LOGIT_RTOL = 0.05
CHECK_REQUESTS = 4
CHECK_PROMPT_MAX = 256
TRACE_SLICE_S = 3.0


def _post(port, prompt, max_tokens, timeout=900):
    """One unstreamed /v1/completions over the socket -> (status, tokens)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(
            {"prompt": prompt, "max_tokens": max_tokens}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        return resp.status, data.decode(errors="replace")
    return 200, json.loads(data)["choices"][0]["tokens"]


def _post_all(port, reqs):
    """POST every (prompt, max_tokens) at once; [(status, tokens)]."""
    out = [None] * len(reqs)

    def one(i):
        try:
            out[i] = _post(port, *reqs[i])
        except (OSError, http.client.HTTPException, ValueError) as e:
            out[i] = (-1, repr(e))
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def teacher_forced_logits(eng, seqs, n_last):
    """For each token sequence, float32 logits at its last ``n_last[i]``
    positions through THIS engine's compiled budget core (the block step the
    scheduler runs, built with ``full_logits``): the sequence goes in as
    C-column chunks, written to and attended through the paged pool. After
    ``chip_smoke._teacher_forced_logits``; the pool is DONATED to the probe
    and handed back to the idle engine afterwards (at 48 slots a copy of it
    would not fit beside it), so call this only while no request is live."""
    import jax
    import jax.numpy as jnp
    dec, b, c = eng.dec, eng.num_slots, eng._budget_cols
    if len(seqs) > b:
        raise ValueError(f"{len(seqs)} sequences, {b} rows")
    nblk = eng.smax // eng.prefill_cap
    if eng.pool.num_blocks < b * nblk or eng.pool.used:
        raise RuntimeError("logit probe needs an idle, full-size pool")
    tables = np.arange(b * nblk, dtype=np.int32).reshape(b, nblk)
    core = jax.jit(dec._build_budget_core(c, full_logits=True, chain=True),
                   donate_argnums=(3,))
    stk = dec._stacked()
    e_arrays = [p._data for p in dec._embed_params]
    h_arrays = dec._maybe_quant_head([p._data for p in dec._head_params])
    zero = jnp.zeros(b, jnp.int32)
    fixed = (jnp.full(b, c, jnp.int32), zero, jnp.ones(b, jnp.int32),
             jnp.full(b, -1, jnp.int32), zero, jnp.ones(b, jnp.float32),
             eng._presence_arg(), zero)
    caches = dict(eng._caches, tbl=jnp.asarray(tables))
    lens = np.zeros(b, np.int32)
    got = [[] for _ in seqs]
    for i in range(-(-max(len(p) for p in seqs) // c)):
        toks = np.zeros((b, c), np.int32)
        seg = np.zeros(b, np.int32)
        for r, p in enumerate(seqs):
            part = p[i * c:(i + 1) * c]
            toks[r, :len(part)] = part
            seg[r] = len(part)
        caches, logits = core(stk, e_arrays, h_arrays, caches,
                              jnp.asarray(toks), jnp.asarray(lens),
                              jnp.asarray(seg), *fixed)
        host = np.asarray(logits)       # one fetch: [B, C, V] is ~13 MB
        for r, p in enumerate(seqs):
            lo = max(len(p) - n_last[r], i * c)     # wanted columns here
            if lo < i * c + seg[r]:
                got[r].append(host[r, lo - i * c:seg[r]].astype(np.float32))
        lens = lens + seg
    eng._keep_caches(caches)
    return [np.concatenate(x) for x in got]


class Session:
    """The served system, set up once; ``window`` measures it."""

    def __init__(self, cell_config, seed, chips):
        self.config, self.seed = cell_config, int(seed)
        self.devs = harness.require_chips(chips)
        harness.say(f"compile cache {harness.use_compile_cache()}")
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.serving_cluster import Gateway, LocalReplica, Router
        t = time.monotonic()
        self.family = harness.load_part("models", cell_config["family"])
        self.model = self.family.build(cell_config, self.seed)
        fmt, embed, head = self.model
        self.eng = ServingEngine(fmt, embed, head, **cell_config["engine"])
        self.rep = LocalReplica("replica0", self.eng)
        # the first dispatch of each core compiles inside engine.step():
        # the heartbeat must outlast it
        self.gw = Gateway(Router([self.rep], hb_dead_s=900.0),
                          port=0).start_background()
        harness.say(f"model, engine and gateway up in "
                    f"{time.monotonic() - t:.1f} s")

    def close(self):
        # the replica first: its drive thread holds the lock every handler
        # of the gateway waits on, and in a cell above the knee it would
        # otherwise work off the backlog while the gateway stops
        self.rep.close()
        self.gw.stop()

    # ---------------------------------------------------------- set-up
    def warm_up_and_check(self, traffic):
        """Warm-up, and the comparison that decides ``correct``, before the
        window: seeded requests of the mix go through the gateway and the
        engine; then the engine's teacher-forced logits over every generated
        position against the float32 reference's on the same weights. Logits
        and not greedy tokens: under random weights streams part at bf16
        ties."""
        t = time.monotonic()
        rng = np.random.default_rng(self.seed + 1)
        plen = np.minimum(schedule.stratified(traffic["prompt_tokens"],
                                              CHECK_REQUESTS),
                          CHECK_PROMPT_MAX)
        mtok = schedule.stratified(traffic["max_tokens"], CHECK_REQUESTS)
        reqs = [(rng.integers(1, self.config["vocab_size"], int(n)).tolist(),
                 int(m)) for n, m in zip(plen, mtok[::-1])]
        res = _post_all(self.gw.port, reqs[:1]) + \
            _post_all(self.gw.port, reqs[1:])
        for (st, toks), (_, m) in zip(res, reqs):
            if st != 200 or len(toks) != m:
                raise harness.BenchmarkError(
                    f"warm-up: status {st}, {len(toks)} of {m} tokens")
        harness.say(f"warm-up: {len(reqs)} completions (prompts "
                    f"{plen.tolist()}, answers {mtok[::-1].tolist()}) in "
                    f"{time.monotonic() - t:.1f} s")
        seqs = [p + toks[:-1] for (p, _), (_, toks) in zip(reqs, res)]
        n_last = [len(toks) for _, toks in res]
        with self.rep._lock:
            got = teacher_forced_logits(self.eng, seqs, n_last)
        ref = harness.load_part("reference", self.config["reference"])
        w = self.family.reference_weights(self.model)
        want = [np.asarray(ref.logits(w, s)[-n:], np.float32)
                for s, n in zip(seqs, n_last)]
        finite = all(np.isfinite(a).all() for a in got + want)
        err = max(float(np.abs(a - b).max()) for a, b in zip(got, want))
        scale = max(float(np.abs(b).max()) for b in want)
        # ties the probe to what the engine really served: every served
        # token is the argmax of the probed logits or a near-tie with it
        short = max(float(g[j].max() - g[j][tok])
                    for g, (_, toks) in zip(got, res)
                    for j, tok in enumerate(toks))
        ok = finite and err <= LOGIT_RTOL * scale and short <= 3 * err
        harness.say(
            f"check: engine vs float32 reference over {sum(n_last)} "
            f"generated positions of {len(seqs)} requests: max |diff| "
            f"{err:.4g} / max |ref| {scale:.4g} = {err / scale:.4f} (bound "
            f"{LOGIT_RTOL}); served tokens' largest shortfall from the "
            f"probe's argmax {short:.4g} (near-tie bound {3 * err:.4g}); "
            f"{'ok' if ok else 'NOT OK'} in {time.monotonic() - t:.1f} s")
        return ok

    # ---------------------------------------------------------- window
    def window(self, traffic, seconds, trace, tmp, keep_trace=None):
        """One schedule: ramp, a window of ``seconds``, the drain. Returns
        what was observed, for the metrics and the per-layer readers."""
        import jax
        eng, rep = self.eng, self.rep
        sched = schedule.build(traffic, self.seed, seconds,
                               self.config["vocab_size"])
        sched_path = os.path.join(tmp, "schedule.json")
        out_path = os.path.join(tmp, "records.json")
        with open(sched_path, "w") as f:
            json.dump(sched, f)
        ramp = float(traffic["ramp_s"])
        count_until = ramp + seconds
        hard_stop = count_until + (traffic["drain_s"] if traffic["count"]
                                   == "due_in_window" else 0.0)
        traces0 = eng._traces_total()
        t0 = time.monotonic() + 1.0
        proc = subprocess.Popen(
            [sys.executable, os.path.join(harness.BENCH_DIR, "loadgen.py"),
             "--schedule", sched_path, "--port", str(self.gw.port),
             "--t0", repr(t0), "--count-until", repr(count_until),
             "--hard-stop", repr(hard_stop), "--out", out_path])
        w0, w1 = t0 + ramp, t0 + count_until
        obs = {"kind": "serve", "w0": w0, "w1": w1, "trace": None,
               "samples": []}

        def open_window():
            with rep._lock:
                eng.reset_metrics()

        def close_window():
            with rep._lock:
                obs["engine"] = eng.metrics()
                obs["steps"] = [dict(e) for e in eng.telemetry.steps]
                obs["spans"] = [(s.trace_id, list(s.events))
                                for s in eng.telemetry.spans]

        def sample():
            obs["samples"].append({
                "t": time.monotonic(), "kv_blocks_used": eng.pool.used,
                "kv_blocks_total": eng.pool.num_blocks,
                "queue_depth": eng.queue_depth,
                "occupancy": eng.occupancy})

        trace_dir = keep_trace or os.path.join(tmp, "trace")
        span = {}

        def start_trace():
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=trace_reduce.options())
            span["t0"] = time.monotonic()

        def stop_trace():
            span["t1"] = time.monotonic()
            jax.profiler.stop_trace()

        plan = [(w0, open_window), (w1, close_window)]
        plan += [(w0 + s + 0.5, sample) for s in range(int(seconds))]
        if trace:
            mid = w0 + seconds / 2
            half = min(TRACE_SLICE_S, seconds / 2) / 2
            plan += [(mid - half, start_trace), (mid + half, stop_trace)]
        try:
            for when, act in sorted(plan, key=lambda p: p[0]):
                time.sleep(max(0.0, when - time.monotonic()))
                act()
            proc.wait(timeout=hard_stop - count_until + 60)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if proc.returncode != 0:
            raise harness.BenchmarkError(
                f"load generator exited {proc.returncode}")
        with open(out_path) as f:
            obs["records"] = json.load(f)
        obs["retraces"] = eng._traces_total() - traces0
        obs["step_paths"] = eng.telemetry_snapshot()["weights"]["step_paths"]
        if trace:
            obs["trace"] = trace_reduce.reduce(
                trace_reduce.load(trace_dir), span["t1"] - span["t0"])
        return obs


def report(obs, traffic, seconds):
    """Earlier lines of the output: medians, counts, lateness, the tails
    that are not end-to-end metrics in this cell."""
    s = latency.COUNT[traffic["count"]](obs["records"], obs["w0"], obs["w1"])
    s["serve_tok_s"] = s["tokens_in_window"] / seconds
    harness.say("window: " + json.dumps(s))
    m = obs["engine"]
    harness.say("engine: " + json.dumps({k: m[k] for k in (
        "requests_admitted", "requests_finished", "tokens_emitted",
        "budget_steps", "budget_tokens_used", "budget_padding_tokens",
        "budget_prefill_tokens", "budget_decode_tokens", "queue_p50_s",
        "queue_p99_s", "ttft_p50_s", "kv_blocks_used", "kv_blocks_total",
        "queue_depth", "occupancy")}))
    harness.say(f"retraces {obs['retraces']}, step paths "
                f"{obs['step_paths']!r}, {len(obs['steps'])} dispatches")
    return s


def run(cell, args, t_start):
    cfg, traffic = cell.config, cell.traffic
    tmp = tempfile.mkdtemp(prefix="bench_")
    sess = Session(cfg, args.seed, cell.chips)
    try:
        checked = sess.warm_up_and_check(traffic)
        obs = sess.window(traffic, args.seconds, args.trace, tmp,
                          keep_trace=args.keep)
    finally:
        sess.close()
        shutil.rmtree(tmp, ignore_errors=True)
    s = report(obs, traffic, args.seconds)
    paths_ok = obs["step_paths"] == cfg["expected_step_paths"]
    short = [r["id"] for r in obs["records"] if r["done"]
             and latency.n_tokens(r) != r["max_tokens"]]
    correct = bool(checked and paths_ok and obs["retraces"] == 0
                   and not short)
    if not correct:
        harness.say(f"NOT correct: reference check {checked}, step paths ok "
                    f"{paths_ok}, retraces {obs['retraces']}, finished "
                    f"requests with a wrong token count {short[:5]}")
    values = {"setup_s": obs["w0"] - t_start,
              "ttft_p95_ms": s["ttft_p95_ms"], "tpot_p95_ms": s["tpot_p95_ms"],
              "serve_tok_s": s["serve_tok_s"]}
    return {"correct": correct, "attempted": s["attempted"],
            "failed": s["failed"], "values": values, "obs": obs,
            "devs": sess.devs}
