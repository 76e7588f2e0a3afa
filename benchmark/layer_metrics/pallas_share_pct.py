"""kernels: device time inside Pallas (Mosaic ``tpu_custom_call``) kernels /
device busy time, from the traced slice. Per-kernel time and roofline share
wait until the program's ``pallas_call``s carry a ``name=``."""


def read(obs):
    t = obs.get("trace")
    return 100.0 * t["pallas_s"] / t["busy_s"] if t and t["busy_s"] else None
