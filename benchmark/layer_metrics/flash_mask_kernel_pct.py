"""kernels: of the traces of attention under a STRUCTURED mask in this
process (``F.scaled_dot_product_attention(..., structured_mask=)``), the
share that took the Pallas flash kernels and not the XLA composite, which
builds the dense mask: 100 x ``paddle_flash_mask_kernel_traces_total`` /
(that + ``paddle_flash_mask_composite_traces_total``). 100 on a TPU at the
published head sizes; anything less means a shape, a dtype, a dropout or a
mesh that ``flash_attention.is_supported`` refuses. Nothing from a program
that has no such call or no such counters (before PR 32)."""


def read(obs):
    from paddle_tpu.inference import telemetry
    kernel, composite = (
        telemetry.runtime_counter(f"paddle_flash_mask_{which}_traces_total", 0)
        for which in ("kernel", "composite"))
    if kernel + composite == 0:
        return None
    return 100.0 * kernel / (kernel + composite)
