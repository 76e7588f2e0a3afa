"""gateway / router: what HTTP, the gateway and the router add to a
request's first token. Median over the window's requests of (first token at
the client - the request's send) - (the engine's own first_token - queued
for the same request), matched by the id the generator sends as
``X-Request-Id``, which the gateway threads into the engine's request span
as its ``trace_id``."""
from statistics import median


def read(obs):
    if obs.get("kind") != "serve" or not obs.get("spans"):
        return None
    engine = {}
    for trace_id, events in obs["spans"]:
        t = dict((name, when) for name, when in reversed(events))
        if "queued" in t and "first_token" in t:
            engine[trace_id] = t["first_token"] - t["queued"]
    diffs = [r["events"][0][0] - r["send"] - engine[r["id"]]
             for r in obs["records"]
             if r["events"] and r["id"] in engine
             and obs["w0"] <= r["due"] < obs["w1"]]
    return 1e3 * median(diffs) if diffs else None
