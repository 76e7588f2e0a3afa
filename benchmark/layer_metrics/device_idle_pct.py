"""device: 1 - (union of the device's operation intervals / the traced
window), from the traced slice."""


def read(obs):
    t = obs.get("trace")
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
