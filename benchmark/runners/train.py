"""Runner ``train``: a model family's compiled training step on fresh batches,
one dispatch per step, for a fixed window.

Construction follows ``chip_smoke.py::phase_trainer`` (PR 21's chip-proven
path) and the clock follows ``bench.py::_timed_steps`` (host clock closed by
fetching the last loss). What is new: a fresh batch from the seed every step,
made on the host and fed while the previous step runs; a window of
``--seconds``; the float32 reference.

Set-up (inside ``setup_s``): the model and its optimizer from the seed, the
comparison with the reference in eval mode, the step's two compiles
(``to_static`` traces once to create the optimizer's slots and once more for
the steady signature), ``warm_steps`` steps.
"""
from __future__ import annotations

import math
import os
import shutil
import tempfile
import time

import numpy as np

from benchmark import harness, trace_reduce

# The program's eval-mode logits (bf16 parameters and activations, float32
# LayerNorm statistics, softmax and loss) against the float32 reference on
# the same weights, as max |diff| over max |reference logit|: the same bound
# and the same reason as the serving runner's (bf16 rounds at 2**-9 a value,
# twelve layers deep; a path in less than bf16 fails it).
LOGIT_RTOL = 0.05
# The mean loss over the check's tokens averages those errors out: bf16
# against float32 differs in the third decimal of a loss near 10.8.
LOSS_RTOL = 0.002
HOST_SPANS = ("feed_batch", "train_step")


def check(model, family, cfg, x, y):
    """Before the window: eval-mode logits and loss of the program on
    ``x``/``y`` (a few seeded sequences) against the reference's."""
    import paddle_tpu as paddle
    t = time.monotonic()
    ref = harness.load_part("reference", cfg["reference"])
    model.eval()
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(x))._data, np.float32)
        got_loss = float(np.asarray(model(
            paddle.to_tensor(x), labels=paddle.to_tensor(y))._data,
            np.float32))
    model.train()
    w = family.reference_weights(model)
    err = scale = 0.0
    losses = []
    for i in range(x.shape[0]):
        want = np.asarray(ref.logits(w, x[i]), np.float32)
        err = max(err, float(np.abs(got[i] - want).max()))
        scale = max(scale, float(np.abs(want).max()))
        losses.append(float(ref.loss(w, x[i], y[i])))
    want_loss = float(np.mean(losses))
    ok = (np.isfinite(got).all() and err <= LOGIT_RTOL * scale
          and abs(got_loss - want_loss) <= LOSS_RTOL * want_loss)
    harness.say(
        f"check: eval-mode program vs float32 reference on {x.shape[0]} x "
        f"{x.shape[1]} tokens: logits max |diff| {err:.4g} / max |ref| "
        f"{scale:.4g} = {err / scale:.4f} (bound {LOGIT_RTOL}); loss "
        f"{got_loss:.5f} vs {want_loss:.5f} (bound {LOSS_RTOL} of it); "
        f"{'ok' if ok else 'NOT OK'} in {time.monotonic() - t:.1f} s")
    return bool(ok)


def run(cell, args, t_start):
    import jax
    import paddle_tpu as paddle
    cfg, traffic = cell.config, cell.traffic
    devs = harness.require_chips(cell.chips)
    harness.say(f"compile cache {harness.use_compile_cache()}")
    b, s = int(traffic["batch"]), int(traffic["seq"])
    vocab = cfg["vocab_size"]
    rng = np.random.default_rng(int(args.seed))

    def fresh():
        ids = rng.integers(0, vocab, (b, s + 1), dtype=np.int32)
        return paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])

    t = time.monotonic()
    family = harness.load_part("models", cfg["family"])
    model, step = family.build(cfg, args.seed)
    harness.say(f"model and optimizer in {time.monotonic() - t:.1f} s")
    ids = rng.integers(0, vocab, (int(traffic["check_sequences"]), s + 1),
                       dtype=np.int32)
    checked = check(model, family, cfg, ids[:, :-1], ids[:, 1:])

    warm = []
    for i in range(2 + int(traffic["warm_steps"])):
        t = time.monotonic()
        x, y = fresh()
        loss = step(x, y)
        warm.append(float(np.asarray(loss._data, np.float32)))
        harness.say(f"warm step {i + 1}: loss {warm[-1]:.4f} "
                    f"({time.monotonic() - t:.2f} s)")
    in_step = "tpu_custom_call" in step.lower(x, y).as_text()

    tmp = tempfile.mkdtemp(prefix="bench_")
    trace_dir = args.keep or os.path.join(tmp, "trace")
    trace, traced_tok_s = None, None
    losses, pending, n = [], None, 0

    def one():
        nonlocal pending, n
        with jax.profiler.TraceAnnotation("feed_batch"):
            x, y = fresh()
        with jax.profiler.TraceAnnotation("train_step"):
            loss = step(x, y)._data
        losses.append(loss)
        if pending is not None:         # at most one step ahead of the chip
            jax.block_until_ready(pending)
        pending = loss
        n += 1

    try:
        t0 = time.monotonic()
        while time.monotonic() - t0 < args.seconds:
            if (args.trace and trace is None
                    and time.monotonic() - t0 >= args.seconds / 2):
                jax.block_until_ready(pending)
                jax.profiler.start_trace(trace_dir,
                                     profiler_options=trace_reduce.options())
                ta = time.monotonic()
                for _ in range(int(traffic["trace_steps"])):
                    one()
                jax.block_until_ready(pending)
                tb = time.monotonic()
                jax.profiler.stop_trace()
                traced_tok_s = traffic["trace_steps"] * b * s / (tb - ta)
                trace = trace_reduce.reduce(trace_reduce.load(trace_dir),
                                            tb - ta, HOST_SPANS)
            one()
        jax.block_until_ready(pending)
        t1 = time.monotonic()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    vals = [float(np.asarray(a, np.float32)) for a in losses]
    bad = sum(not math.isfinite(v) for v in vals)
    near = abs(warm[0] - math.log(cfg["padded_vocab_size"])) <= \
        0.05 * math.log(cfg["padded_vocab_size"])
    tok_s = n * b * s / (t1 - t0)
    harness.say(f"window: {n} steps of {b} x {s} in {t1 - t0:.3f} s = "
                f"{tok_s:.1f} tokens/s, {1e3 * (t1 - t0) / n:.2f} ms a step; "
                f"losses {vals[0]:.4f} .. {vals[-1]:.4f}; first loss of all "
                f"{warm[0]:.4f} (ln vocabulary "
                f"{math.log(cfg['padded_vocab_size']):.4f}); kernel in the "
                f"lowered step: {in_step}")
    correct = bool(checked and not bad and near and in_step)
    obs = {"kind": "train", "trace": trace, "train": {
        "flops_per_token": family.flops_per_token(cfg, s),
        "traced_tok_s": traced_tok_s, "chips": cell.chips,
        "peak_bf16_flops": harness.peaks(devs[0].device_kind)["bf16_flops"]}}
    return {"correct": correct, "attempted": n, "failed": bad,
            "values": {"setup_s": t0 - t_start, "train_tok_s": tok_s},
            "obs": obs, "devs": devs}
