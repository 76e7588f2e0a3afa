"""trainer: of the Pallas kernel forwards met while a ``fleet.utils.recompute``
region's replay was traced in this process, the share that the replay took
from what its first forward had kept, and did not compute again: 100 x
``paddle_recompute_kept_total`` / (that +
``paddle_recompute_replayed_total``). 100 on one chip; less on a mesh of
several devices, where the flash kernel runs inside ``shard_map`` and the
replay computes it again. Nothing from a program that replays no kernel or
has no such counters (before PR 33)."""


def read(obs):
    from paddle_tpu.inference import telemetry
    kept, replayed = (
        telemetry.runtime_counter(f"paddle_recompute_{which}_total", 0)
        for which in ("kept", "replayed"))
    if kept + replayed == 0:
        return None
    return 100.0 * kept / (kept + replayed)
