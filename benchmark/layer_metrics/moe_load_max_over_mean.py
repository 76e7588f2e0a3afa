"""experts: how unevenly the router loads the experts held here: rows of the
fullest held expert / mean rows of the held experts, per expert layer, mean
over the layers. 1 is an even router; the grouped product's time follows the
rows, so an uneven load is time a rank of an expert-parallel group waits.

The three ``moe_*`` readers share what is here: the program's own routing
counts (``routing_stats()``, kept on the device, one transfer when read) of
the expert layers alive (the family file keeps the model it built), counted
since the model was built: the comparison's two forwards, the warm-up and
the window, which is nine tenths of it. Nothing from a program that keeps no
such counts (before PR 28) or that holds no such layer."""


def stats():
    """``routing_stats()`` of the program, or None."""
    try:
        from paddle_tpu.incubate.distributed.models import moe
    except ImportError:
        return None
    read = getattr(moe, "routing_stats", None)
    out = None if read is None else read()
    return out if out and out["layers"] else None


def load_ratio(st):
    per_layer = [max(r["rows_per_expert"]) * len(r["rows_per_expert"])
                 / sum(r["rows_per_expert"])
                 for r in st["layers"] if sum(r["rows_per_expert"])]
    return sum(per_layer) / len(per_layer) if per_layer else None


def read(obs):
    st = stats()
    return None if st is None else load_ratio(st)
