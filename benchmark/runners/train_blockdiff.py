"""Runner ``train_blockdiff``: runner ``train`` (same set-up, warm-up, window,
trace and result) for a model trained by diffusion over blocks, whose forward
pass takes a draw of noise beside the tokens.

``train.check`` compares ``model(x)`` with ``reference.logits(w, x)``. Here
both need the same draw: ``noise`` draws ``masked`` and ``t`` from the run's
seed on the host, the program runs in eval mode on them
(``model(x, masked, t)``: the noisy half's logits; with ``labels`` the
weighted loss), and the reference computes the same two from the same draw.
The model routes through a top-k expert layer, so the verdict starts from
``train_topk.compare``'s (all but 0.01 of the tokens within 0.05 of the
largest reference logit, the mean loss within 0.002). Its bound on EVERY
token, 0.2, does not stand between this model's two readings, so ``compare``
here holds every token to ``TOKEN_RTOL``. Each limit lies between the bf16
program's reading and the float8 CONTROL's (the reference itself with every
weight and every layer's input rounded to float8 e4m3 in the program's
place). This file run as a script computes the control on what a run of the
cell compares, and fails unless ``compare`` refuses it::

    python3 -m benchmark.runners.train_blockdiff --workload <cell> --seed <n> ...

Readings on the chip at the published widths, 1 x 8,192 data tokens (chip
runs of PR 32; PERF.md section 2). The bf16 program over 9 seeds: share of
tokens past 0.05 0.0 on every seed, largest difference 0.0210-0.0291 of the
largest reference logit, loss within 2.4e-5 of the reference's. The float8
control on 3 of those seeds: share 1.0, largest 0.1218 / 0.1254 / 0.1336,
loss within 2.3e-5 to 1.2e-4 (the loss alone does not tell float8 from
bf16; each of the two logit limits does).

A traced run also gives ``obs["kernels"]``: seconds of device self time by
``pallas_call`` name over the traced steps, which ``trace_reduce.reduce``'s
ten largest operations cannot hold (six layers' kernels are more than ten).
``train_tok_s`` counts DATA tokens (``batch x seq`` a step), not positions.
"""
from __future__ import annotations

import argparse
import functools
import os
import shutil
import tempfile
import time
from collections import defaultdict
from unittest import mock

import numpy as np

from benchmark import harness, trace_reduce
from benchmark.runners import train, train_topk


# The bound on every token, of the largest reference logit: the bf16 program
# reads at most 0.0291 (2.7 x below), the float8 control at least 0.1218 (1.5
# x above). No flipped expert choice showed in 9 x 8,192 tokens (Qwen3-Next's
# flips read 0.06-0.11); the room above the program is for one.
TOKEN_RTOL = 0.08


def compare(got, want, got_loss, want_loss):
    """(ok, readings): ``train_topk.compare`` with every token held to
    ``TOKEN_RTOL``."""
    ok, r = train_topk.compare(got, want, got_loss, want_loss)
    return bool(ok and r["worst"] <= TOKEN_RTOL * r["scale"]), r


def _say(what, x, r, got_loss, want_loss, ok, t0):
    harness.say(
        f"check: {what} vs float32 reference on {x.shape[0]} x "
        f"{x.shape[1]} tokens: share of tokens past {train.LOGIT_RTOL} of "
        f"max |ref| {r['scale']:.4g}: {r['share']:.5f} (bound "
        f"{train_topk.FLIP_SHARE}); largest |diff| {r['worst']:.4g} = "
        f"{r['worst'] / r['scale']:.4f} of it (bound {TOKEN_RTOL}); loss "
        f"{got_loss:.5f} vs {want_loss:.5f} (bound {train.LOSS_RTOL} of it); "
        f"{'ok' if ok else 'NOT OK'} in {time.monotonic() - t0:.1f} s")


def noise(cfg, seed, batch, seq):
    """The comparison's draw, from the seed on the host: (masked [B, L]
    bool, t [B, blocks] float32) by the configuration's schedule."""
    rng = np.random.default_rng([int(seed), 0xB10C])
    b, eps = int(cfg["block_length"]), float(cfg["noise_eps"])
    t = (eps + (1.0 - eps) * rng.random((batch, -(-seq // b)))).astype(
        np.float32)
    masked = rng.random((batch, seq)) < t[:, np.arange(seq) // b]
    return masked, t


def reference(ref, w, x, masked, t, low=None):
    """The reference's noisy-half logits [B, L, V] and mean weighted loss on
    ``x`` under the draw, one forward a sequence (in ``low``: the
    control)."""
    lg = [ref.logits(w, x[i], masked[i], low) for i in range(x.shape[0])]
    losses = [float(ref.token_loss(a, x[i], masked[i], t[i],
                                   w["block_length"]))
              for i, a in enumerate(lg)]
    return (np.stack([np.asarray(a, np.float32) for a in lg]),
            float(np.mean(losses)))


def check(model, family, cfg, x, y, seed):
    """``train.check``'s computation on one shared draw of the noise, under
    ``compare``'s limits. ``y`` (the next tokens) is not used: position
    i restores token i."""
    import paddle_tpu as paddle
    t0 = time.monotonic()
    ref = harness.load_part("reference", cfg["reference"])
    masked, t = noise(cfg, seed, *x.shape)
    model.eval()
    with paddle.no_grad():
        ids = paddle.to_tensor(x)
        got = np.asarray(model(ids, masked, t)._data, np.float32)
        got_loss = float(np.asarray(
            model(ids, masked, t, labels=ids)._data, np.float32))
    model.train()
    want, want_loss = reference(ref, family.reference_weights(model), x,
                                masked, t)
    ok, r = compare(got, want, got_loss, want_loss)
    _say("eval-mode program", x, r, got_loss, want_loss, ok, t0)
    return ok


def control(cell, seed):
    """``compare``'s verdict on the reference in ``train_topk.CONTROL``
    precision in the program's place, on the weights, sequences and draw a
    run of ``cell`` with ``seed`` compares: it has to be refused."""
    t0 = time.monotonic()
    cfg = cell.config
    family = harness.load_part("models", cfg["family"])
    ref = harness.load_part("reference", cfg["reference"])
    w = family.reference_weights(family.build(cfg, seed)[0])
    x, _ = train_topk.check_sequences(cell, seed)
    masked, t = noise(cfg, seed, *x.shape)
    want, want_loss = reference(ref, w, x, masked, t)
    got, got_loss = reference(ref, w, x, masked, t, train_topk.CONTROL)
    ok, r = compare(got, want, got_loss, want_loss)
    _say(f"control (seed {seed}): reference in {train_topk.CONTROL}", x, r,
         got_loss, want_loss, ok, t0)
    return ok


def kernel_seconds(trace):
    """Seconds of device self time by Pallas kernel (the ``name=`` of its
    ``pallas_call`` as the compiled program wraps it, the number XLA gives
    each instance taken off), mean over the chips."""
    planes = [p for p in trace["planes"]
              if trace_reduce.DEVICE_PLANE.match(p["name"])]
    out = defaultdict(float)
    for plane in planes:
        events = [e for ln in plane["lines"]
                  if ln["name"] == trace_reduce.OPS_LINE
                  for e in ln["events"]]
        for name, ns in trace_reduce.self_times(events).items():
            if trace_reduce.is_pallas(name):
                op = trace_reduce.short(name).split(" ")[0]
                out[op.rsplit(".", 1)[0]] += ns / len(planes) / 1e9
    return dict(out)


def run(cell, args, t_start):
    """``train.run`` with this file's ``check`` in its place; traced, the
    trace is kept until its kernels have been read by name."""
    own = None
    if args.trace and not args.keep:
        own = tempfile.mkdtemp(prefix="bench_blockdiff_")
        args = argparse.Namespace(**{**vars(args), "keep": own})
    try:
        with mock.patch.object(train, "check", functools.partial(
                check, seed=args.seed)):
            res = train.run(cell, args, t_start)
        obs = res["obs"]
        if args.trace and obs["trace"] is not None:
            obs["kernels"] = kernel_seconds(trace_reduce.load(args.keep))
            family = harness.load_part("models", cell.config["family"])
            seq = int(cell.traffic["seq"])
            obs["train"]["attention_flops_per_token"] = \
                family.attention_flops(cell.config, seq)
            obs["train"]["traced_tokens"] = (
                int(cell.traffic["trace_steps"]) * int(cell.traffic["batch"])
                * seq)
            harness.say("kernels (s over the traced steps): " + ", ".join(
                f"{k} {v:.4f}" for k, v in sorted(obs["kernels"].items())))
    finally:
        if own:
            shutil.rmtree(own, ignore_errors=True)
    return res


if __name__ == "__main__":
    import sys
    ap = argparse.ArgumentParser(description="the float8 control of a cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--manifest", default=os.path.join(harness.REPO,
                                                       "BENCHMARK.json"))
    a = ap.parse_args()
    passed = [s for s in a.seed
              if control(harness.Cell(a.manifest, a.workload), s)]
    if passed:
        sys.exit(f"the comparison accepts the control on seeds {passed}: "
                 "its limits are too wide")
