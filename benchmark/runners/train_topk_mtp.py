"""Runner ``train_topk_mtp``: runner ``train_topk_scoped`` (the same set-up,
warm-up, window, result and kept trace) for a model whose eval-mode forward
returns a second head's logits behind the first's along the tokens (a
multi-token-prediction module: the main head's T rows, then the MTP head's
T - 1), under this file's limits, with the comparison's readings also said
for each head apart.

``train_topk_scoped``'s bound on every token, 0.14, does not stand above
this model's reading with room. JoyAI-LLM-Flash at the published widths, 2 x
8,192 tokens, on the chip over nine seeds (PERF.md section 2): the bf16
program puts 0.0129-0.0236 of the tokens past 0.05 of the largest reference
logit, its largest difference 0.0997-0.1433 of it (mean 0.123, standard
deviation 0.013; always in the main head's rows, the MTP head's reading
0.092-0.099); the float8 control (the reference with every weight and layer
input rounded to e4m3) on six seeds puts 1.0 of them past 0.05, its largest
0.1699-0.2069 (mean 0.191, deviation 0.012), and its loss within 1.2e-3 of
the reference's (the loss alone does not tell float8 from bf16). The two
largest differences overlap in their tails, so the bound on every token
stands nearer the control's: 3.2 of the program's deviations above its mean
and 2.1 of the control's below its own, because a program run past it
refuses the cell, while the share refuses the control with twentyfold room
anyway (the share keeps ``train_topk_scoped``'s 0.05). This file run as a
script computes the control on what a run of the cell compares and fails
unless the comparison refuses it::

    python3 -m benchmark.runners.train_topk_mtp --workload <cell> --seed <n>
"""
from __future__ import annotations

import argparse
import os
from unittest import mock

import numpy as np

from benchmark import harness
from benchmark.runners import train, train_topk, train_topk_scoped

# The share of tokens past 0.05 (program at most 0.0236, 2.1 x below;
# control 1.0) and the bound on every token (program at most 0.1433, 1.15 x
# below; control at least 0.1699, 1.03 x above).
LIMITS = {"FLIP_SHARE": 0.05, "FLIP_RTOL": 0.165}

_compare = train_topk.compare


def compare(got, want, got_loss, want_loss):
    """``train_topk.compare``; the share and the largest difference of the
    main head's rows and of the MTP head's are said apart, against the
    same scale."""
    ok, r = _compare(got, want, got_loss, want_loss)
    t, scale = (got.shape[1] + 1) // 2, r["scale"]
    parts = []
    for name, rows in (("main", slice(0, t)), ("mtp", slice(t, None))):
        per_token = np.abs(got[:, rows] - want[:, rows]).max(axis=-1)
        share = float((per_token > train.LOGIT_RTOL * scale).mean())
        parts.append(f"{name} rows: share {share:.5f}, largest "
                     f"{float(per_token.max()) / scale:.4f}")
    harness.say("check by head: " + "; ".join(parts))
    return ok, r


def limits():
    """``train_topk``'s comparison, its check and its control, under
    ``LIMITS``, with the readings by head."""
    return mock.patch.multiple(train_topk, compare=compare, **LIMITS)


def run(cell, args, t_start):
    """``train_topk_scoped.run`` under this file's limits."""
    with mock.patch.object(train_topk_scoped, "limits", limits):
        return train_topk_scoped.run(cell, args, t_start)


if __name__ == "__main__":
    import sys
    ap = argparse.ArgumentParser(description="the float8 control of a cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--manifest", default=os.path.join(harness.REPO,
                                                       "BENCHMARK.json"))
    a = ap.parse_args()
    with limits():
        passed = [s for s in a.seed if train_topk.control(
            harness.Cell(a.manifest, a.workload), s)]
    if passed:
        sys.exit(f"the comparison accepts the control on seeds {passed}: "
                 "its limits are too wide")
