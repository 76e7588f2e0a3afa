"""The one general traffic generator: a traffic file's parameters plus a
seed give the schedule of requests.

Every seed gets the SAME work in another order. The schedule is built in
blocks of ``block`` requests; inside a block the prompt lengths, the answer
lengths and the gaps between arrivals are the block's stratified quantiles
of their distributions (the same multiset in every block and for every
seed), and the seed only permutes each of the three and draws the token
ids. So two seeds differ in order and content, never in how much they ask
of the system, and any stretch of ``block`` requests carries the same load.

Traffic file (``kind: open_loop_completions``)::

    {"kind": "open_loop_completions",
     "rate_per_s": 2.0,              # offered rate, fixed: never searched
     "arrivals": "poisson",          # exponential gaps; "uniform": equal;
                                     # "bursts": Poisson groups of "burst"
                                     # requests 10 ms apart
     "block": 24,
     "ramp_s": 6.0,                  # schedule runs this long before the
                                     # window opens; part of set-up
     "drain_s": 15.0,                # after the window closes (see count)
     "count": "due_in_window",       # or "finished_in_window"
     "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                       "min": 32, "max": 768},
     "max_tokens":    {"dist": "lognormal", "median": 96, "sigma": 0.7,
                       "min": 16, "max": 224},
     "shared_prefix_tokens": 0,      # > 0: every prompt starts with the
                                     # same seeded prefix of that length
     "session_turns": 1}             # > 1: that many requests in a row are
                                     # turns of one session and share ITS
                                     # seeded prefix of shared_prefix_tokens
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def stratified(spec, n):
    """``n`` values at the stratified quantiles of ``spec``'s distribution,
    clipped to its [min, max], as whole numbers, ascending."""
    q = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        v = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown dist {spec['dist']!r}")
    lo = spec.get("min", -math.inf)
    hi = spec.get("max", math.inf)
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def gaps(arrivals, rate, n, burst=1):
    """``n`` gaps between arrivals with mean exactly 1 / rate."""
    if arrivals == "poisson":
        g = -np.log1p(-_quantiles(n))
        return g / g.mean() / rate
    if arrivals == "uniform":
        return np.full(n, 1.0 / rate)
    if arrivals == "bursts":
        if n % burst:
            raise ValueError(f"block {n} is not a multiple of burst {burst}")
        g = np.full(n, 0.01)
        lead = gaps("poisson", 1.0, n // burst)
        g[::burst] = lead * (n / rate - 0.01 * (n - n // burst)) / lead.sum()
        return g
    raise ValueError(f"unknown arrivals {arrivals!r}")


def build(traffic, seed, seconds, vocab):
    """The schedule for one run: a list of requests ``{"id", "due_s",
    "prompt", "max_tokens"}``, ``due_s`` counted from the schedule's start.
    It covers the ramp, the window and the time after it in which counted
    requests may still finish, so that they finish under the same load."""
    rng = np.random.default_rng(int(seed))
    total_s = traffic["ramp_s"] + seconds + traffic["drain_s"]
    rate, k = float(traffic["rate_per_s"]), int(traffic["block"])
    n_blocks = int(math.ceil(total_s * rate / k)) + 1
    plen = stratified(traffic["prompt_tokens"], k)
    mtok = stratified(traffic["max_tokens"], k)
    burst = int(traffic.get("burst", 1))
    gap = gaps(traffic["arrivals"], rate, k, burst)
    n_shared = int(traffic.get("shared_prefix_tokens", 0))
    turns = int(traffic.get("session_turns", 1))
    shared = rng.integers(1, vocab, n_shared).tolist()
    out, t = [], 0.0
    for _ in range(n_blocks):
        p, m = rng.permutation(plen), rng.permutation(mtok)
        # whole bursts change places, so that a burst stays a burst
        g = rng.permutation(gap.reshape(-1, burst)).reshape(-1)
        for i in range(k):
            t += float(g[i])
            if turns > 1 and len(out) % turns == 0:
                shared = rng.integers(1, vocab, n_shared).tolist()
            own = max(int(p[i]) - n_shared, 1)
            out.append({
                "id": f"r{len(out):05d}", "due_s": t,
                "prompt": shared + rng.integers(1, vocab, own).tolist(),
                "max_tokens": int(m[i])})
    return [r for r in out if r["due_s"] < total_s]
