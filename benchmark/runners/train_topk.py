"""Runner ``train_topk``: runner ``train`` (same set-up, warm-up, window,
trace and result) for a model whose layers CHOOSE experts by top-k, with a
comparison that knows what a choice does to a comparison.

``train.check`` holds the largest logit difference over all tokens to 0.05 of
the largest reference logit. A dense model in bf16 reads 0.011 there (GPT-2
124M, PERF.md section 2). A top-k router is not continuous: where a token's
last chosen expert and its first unchosen one lie closer than bf16's rounding
of the hidden state moves their logits, the bf16 program and the float32
reference choose differently, one whole expert's weighted result is in one
and not in the other, and that token and the three after it (the reach of
the Gated DeltaNet's convolution) differ by that much however carefully
everything else is computed. Qwen3-Next at its published widths reads
0.06-0.11 under ``train.check`` on the chip (PERF.md section 6, PR 28). So
here:

* all but ``FLIP_SHARE`` of the tokens lie within ``train.LOGIT_RTOL`` (0.05)
  of the largest reference logit: the body of the distribution is held to the
  dense bound;
* EVERY token lies within ``FLIP_RTOL`` of it: a flipped choice moves a token
  by one expert's share, not by more;
* the mean loss lies within ``train.LOSS_RTOL``, as in ``train``.

Each of the two new limits lies between what the bf16 program reads and
what the CONTROL reads: the reference itself with every weight and every
layer's input rounded to float8 (e4m3, the nearest precision below bf16)
put in the program's place. This file run as a script computes the control
on the sequences a run of the cell compares, and fails unless ``compare``
refuses it::

    python3 -m benchmark.runners.train_topk --workload <cell> --seed <n> ...

The readings are in PERF.md section 2.
"""
from __future__ import annotations

import time
from unittest import mock

import numpy as np

from benchmark import harness
from benchmark.runners import train

# Tokens allowed past train.LOGIT_RTOL. The bf16 program on the chip reads
# 0.0006-0.0028 (10 to 46 of 16,384 tokens); the float8 control reads 1.0.
FLIP_SHARE = 0.01
# The bound on every token. bf16 reads 0.063-0.106: a largest value over
# 16,384 tokens, so it has a tail, and the room above it is twofold; the
# float8 control reads 0.43-0.46 (its BEST token 0.12).
FLIP_RTOL = 0.2
CONTROL = "float8_e4m3fn"


def compare(got, want, got_loss, want_loss):
    """(ok, readings) of the program's logits [B, T, V] and mean loss
    against the reference's."""
    scale = float(np.abs(want).max())
    per_token = np.abs(got - want).max(axis=-1)
    share = float((per_token > train.LOGIT_RTOL * scale).mean())
    worst = float(per_token.max())
    ok = (np.isfinite(got).all() and share <= FLIP_SHARE
          and worst <= FLIP_RTOL * scale
          and abs(got_loss - want_loss) <= train.LOSS_RTOL * want_loss)
    return bool(ok), {"scale": scale, "share": share, "worst": worst}


def reference(ref, w, x, y, low=None):
    """The reference's logits [B, T, V] and mean loss on ``x``/``y``, one
    forward a sequence (in ``low``: the control)."""
    lg = [ref.logits(w, x[i], low) for i in range(x.shape[0])]
    losses = [float(ref.token_loss(a, y[i])) for i, a in enumerate(lg)]
    return (np.stack([np.asarray(a, np.float32) for a in lg]),
            float(np.mean(losses)))


def check_sequences(cell, seed):
    """The sequences ``train.run`` draws for its comparison: the seed's
    first draw."""
    ids = np.random.default_rng(int(seed)).integers(
        0, cell.config["vocab_size"],
        (int(cell.traffic["check_sequences"]), int(cell.traffic["seq"]) + 1),
        dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def _say(what, x, r, got_loss, want_loss, ok, t):
    harness.say(
        f"check: {what} vs float32 reference on {x.shape[0]} x "
        f"{x.shape[1]} tokens: share of tokens past {train.LOGIT_RTOL} of "
        f"max |ref| {r['scale']:.4g}: {r['share']:.5f} (bound {FLIP_SHARE}); "
        f"largest |diff| {r['worst']:.4g} = {r['worst'] / r['scale']:.4f} of "
        f"it (bound {FLIP_RTOL}); loss {got_loss:.5f} vs {want_loss:.5f} "
        f"(bound {train.LOSS_RTOL} of it); {'ok' if ok else 'NOT OK'} in "
        f"{time.monotonic() - t:.1f} s")


def check(model, family, cfg, x, y):
    """``train.check``'s computation under this file's limits."""
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
    want, want_loss = reference(ref, family.reference_weights(model), x, y)
    ok, r = compare(got, want, got_loss, want_loss)
    _say("eval-mode program", x, r, got_loss, want_loss, ok, t)
    return ok


def control(cell, seed):
    """``compare``'s verdict on the reference in ``CONTROL`` precision in
    the program's place, on the weights and sequences a run of ``cell`` with
    ``seed`` compares: it has to be refused."""
    t = time.monotonic()
    cfg = cell.config
    family = harness.load_part("models", cfg["family"])
    ref = harness.load_part("reference", cfg["reference"])
    w = family.reference_weights(family.build(cfg, seed)[0])
    x, y = check_sequences(cell, seed)
    want, want_loss = reference(ref, w, x, y)
    got, got_loss = reference(ref, w, x, y, CONTROL)
    ok, r = compare(got, want, got_loss, want_loss)
    _say(f"control (seed {seed}): reference in {CONTROL}", x, r, got_loss,
         want_loss, ok, t)
    return ok


def run(cell, args, t_start):
    """``train.run``, which calls its module's ``check`` by name, with this
    file's in its place."""
    with mock.patch.object(train, "check", check):
        return train.run(cell, args, t_start)


if __name__ == "__main__":
    import argparse
    import os
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
