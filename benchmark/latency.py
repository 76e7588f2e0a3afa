"""From the load generator's per-request records to what a client saw.

A record (``loadgen.py`` writes one per request; every time is the host's
monotonic clock, in seconds)::

    {"id": "r00012", "due": 812.40, "send": 812.4004, "status": 200,
     "max_tokens": 96, "events": [[813.1, 1], [813.3, 4], ...],
     "done": true, "end": 818.2, "error": null}

``events`` holds one ``[arrival time, tokens in it]`` per SSE event that
carried tokens. ``done`` says the stream ended with ``data: [DONE]``;
``end`` is when the connection closed or the generator gave up on it.
Standard library only: the generator's process imports this file too.
"""
from __future__ import annotations


def percentile(values, q):
    """The ``q``-th percentile (0-100) with linear interpolation between
    order statistics; None for no values."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def n_tokens(rec):
    return sum(n for _, n in rec["events"])


def ttft_s(rec):
    """First streamed token's arrival minus the time the request WAS DUE:
    a generator that runs late, or a server that stalls accepting, shows as
    a longer wait, as it does to a user."""
    return rec["events"][0][0] - rec["due"] if rec["events"] else None


def tpot_s(rec):
    """(last token's arrival - first token's arrival) / tokens after the
    first SSE event: the gap a streaming user sees, whatever the size of
    the engine's decode chunks. None where no token came after the first
    event."""
    ev = rec["events"]
    if len(ev) < 2:
        return None
    return (ev[-1][0] - ev[0][0]) / (n_tokens(rec) - ev[0][1])


def lateness_s(rec):
    """How late the generator itself sent the request."""
    return rec["send"] - rec["due"]


def failed(rec):
    """Refused, non-200, cut short, or not ended by ``[DONE]`` with exactly
    ``max_tokens`` tokens."""
    return (rec["status"] != 200 or rec["error"] is not None
            or not rec["done"] or n_tokens(rec) != rec["max_tokens"])


def _summary(counted, records, w0, w1):
    """Counts and tails over the ``counted`` requests, and the tokens that
    reached any client in the window."""
    out = {"attempted": len(counted),
           "failed": sum(failed(r) for r in counted),
           "tokens_in_window": tokens_between(records, w0, w1)}
    for name, fn in (("ttft", ttft_s), ("tpot", tpot_s),
                     ("lateness", lateness_s)):
        vals = [v for v in (fn(r) for r in counted) if v is not None]
        out[f"{name}_n"] = len(vals)
        for q in (50, 95):
            p = percentile(vals, q)
            out[f"{name}_p{q}_ms"] = None if p is None else 1e3 * p
    return out


def due_in_window(records, w0, w1):
    """Below the knee. Attempted: every request due in [w0, w1). Failed:
    those that ``failed`` (a request still unfinished when the generator
    stopped, ``drain_s`` after the window, has no ``[DONE]``). Tails are
    over all attempted requests that streamed a token."""
    return _summary([r for r in records if w0 <= r["due"] < w1],
                    records, w0, w1)


def finished_in_window(records, w0, w1):
    """Above the knee the backlog grows by design, so a request still in
    flight when the window closes is neither attempted nor failed.
    Attempted: requests whose connection ended in [w0, w1)."""
    return _summary([r for r in records
                     if r["end"] is not None and w0 <= r["end"] < w1],
                    records, w0, w1)


def tokens_between(records, w0, w1):
    """Output tokens that reached any client in [w0, w1)."""
    return sum(n for r in records for t, n in r["events"] if w0 <= t < w1)


COUNT = {"due_in_window": due_in_window,
         "finished_in_window": finished_in_window}
