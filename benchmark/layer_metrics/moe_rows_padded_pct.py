"""experts: the share of the sorted buffer that holds no routed pair, 100 x
(rows of the buffer - pairs routed to the experts held here that fit) / rows
of the buffer. The buffer is the layer's stated bound (``BUFFER_FACTOR`` x an
even router's rows); its rows are gathered, masked and scattered, and the
grouped product skips the tiles past the last expert's rows."""
from benchmark.layer_metrics.moe_load_max_over_mean import stats


def padded_pct(st):
    rows = st["rows_computed"]
    used = st["pairs_local"] - st["pairs_dropped"]
    return 100.0 * (rows - used) / rows if rows else None


def read(obs):
    st = stats()
    return None if st is None else padded_pct(st)
