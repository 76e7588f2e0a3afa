"""trainer: the share of the device's busy time that the multi-token-
prediction module takes: ``_scopes.device_pct`` of the scope component
``mtp``, in every pass (the first forward, the replay of ``recompute`` and
the backward) and every part (the embedding projection, the decoder layer,
the norm, the shared head and the loss). Nothing from a trace with no such
rows (a model without the module, a program without the scope)."""
from benchmark.layer_metrics._scopes import device_pct


def read(obs):
    return device_pct(obs, "mtp")
