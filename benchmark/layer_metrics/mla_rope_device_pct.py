"""kernels: the share of the device's busy time that latent attention's
decoupled rotary takes: ``_scopes.device_pct`` of the scope component
``mla.rope`` (the rotation of the query heads' rotary channels and of the
shared ``k_r``), in every pass. A fusion takes its root's stamp, so what XLA
fused into the rotation counts here and a rotation fused into a neighbour
counts there. Nothing from a trace with no such rows (latent attention
without positions, a program without the scope)."""
from benchmark.layer_metrics._scopes import device_pct


def read(obs):
    return device_pct(obs, "mla.rope")
