"""KV manager: blocks of the paged pool in use / blocks in the pool,
sampled once a second through the window by the runner, median."""
from statistics import median


def read(obs):
    s = [100.0 * x["kv_blocks_used"] / x["kv_blocks_total"]
         for x in obs.get("samples") or [] if x.get("kv_blocks_total")]
    return median(s) if s else None
