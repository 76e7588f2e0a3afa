"""scheduler: median time a request waited in the engine's queue before it
was given a slot, from ``eng.metrics()["queue_p50_s"]`` (a log-bucket
estimate, window only: the runner resets the engine's metrics when the
window opens)."""


def read(obs):
    q = (obs.get("engine") or {}).get("queue_p50_s")
    return None if q is None else 1e3 * q
