"""experts: (token, choice) pairs routed to an expert held here that did not
fit the sorted buffer and were not computed. A dropless layer reads 0; any
other reading means the buffer's bound is too small for this traffic."""
from benchmark.layer_metrics.moe_load_max_over_mean import stats


def read(obs):
    st = stats()
    return None if st is None else st["pairs_dropped"]
