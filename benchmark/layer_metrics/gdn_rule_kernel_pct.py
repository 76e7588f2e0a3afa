"""kernels: of the traces of the gated delta rule in this process, the share
that took the Pallas kernel (``gdn_chunk_rule_fwd``) and not the XLA
composite: 100 x ``paddle_gdn_rule_kernel_traces_total`` / (that +
``paddle_gdn_rule_composite_traces_total``). 100 on a TPU at the published
head sizes; anything less means a shape, a dtype or a mesh the kernel's
``is_supported`` refuses. Nothing from a program that has no such layer or
no such counters (before PR 31)."""


def read(obs):
    from paddle_tpu.inference import telemetry
    kernel, composite = (
        telemetry.runtime_counter(f"paddle_gdn_rule_{which}_traces_total", 0)
        for which in ("kernel", "composite"))
    if kernel + composite == 0:
        return None
    return 100.0 * kernel / (kernel + composite)
