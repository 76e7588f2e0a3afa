"""compiled step: median host milliseconds per engine dispatch, from the
engine's telemetry step timeline: the call into the compiled core
(``dur_s``) plus the harvest that waits for its result (``host_s``)."""
from statistics import median


def read(obs):
    ms = [1e3 * (e["dur_s"] + e.get("host_s", 0.0))
          for e in obs.get("steps") or []]
    return median(ms) if ms else None
