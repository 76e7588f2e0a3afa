"""kernels: of the flash-attention backward passes over SEVERAL tiles a
(batch, head) plane traced in this process, the share that ran as the ONE
kernel ``flash_attention_bwd_onepass`` (S, P, dP and dS once a visited tile,
dQ summed in VMEM beside dK and dV) and not as the dK/dV + dQ pair, which
remains for a plane whose float32 dQ outgrows the kernel's VMEM budget: 100 x
``paddle_flash_bwd_onepass_traces_total`` / (that +
``paddle_flash_bwd_split_traces_total``), counted where
``ops/pallas/flash_attention.py::_bwd`` decides, from shapes alone. Nothing
from a program that traced neither (one tile a plane, the composite) or has
no such counters (before PR 38)."""


def read(obs):
    from paddle_tpu.inference import telemetry
    onepass, split = (
        telemetry.runtime_counter(f"paddle_flash_bwd_{which}_traces_total", 0)
        for which in ("onepass", "split"))
    if onepass + split == 0:
        return None
    return 100.0 * onepass / (onepass + split)
