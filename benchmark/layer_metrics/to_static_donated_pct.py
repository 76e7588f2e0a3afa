"""trainer: the share of its persistent-state leaves that a compiled call
donates to the program (``call_timeline()``'s ``donated`` and ``kept``),
median over the same steady calls as ``to_static_call_ms``: 100 when every
state result takes its input's buffer and the call allocates none of them.
Nothing from a program whose records lack the two fields (before PR 27)."""
from statistics import median

from benchmark.layer_metrics.to_static_call_ms import steady_calls


def read(obs):
    recs = steady_calls()
    if not recs or "donated" not in recs[-1]:
        return None
    return median(100.0 * r["donated"] / max(1, r["donated"] + r["kept"])
                  for r in recs)
