"""Kernel autotune config surface.

Capability parity: python/paddle/incubate/autotune.py :: set_config —
the reference toggles kernel-algorithm search (cuDNN algo search, layout
autotune, dataloader tuning). TPU-native meaning: there is nothing to
search. XLA autotunes its own fusions, and the Pallas kernels' tiles
follow from their shapes (ops/pallas/flash_attention.py::_block_sizes).
set_config keeps the reference's {"kernel": {"enable": ..}, "dataloader":
{...}} dict for get_config() introspection and steers no kernel.
"""
from __future__ import annotations

import json

__all__ = ["set_config", "get_config"]

_config = {"kernel": {"enable": False},
           "layout": {"enable": False},
           "dataloader": {"enable": False}}


def set_config(config=None):
    """Accepts a dict (or a path to a JSON file, like the reference) and
    records it for get_config(); None enables every section. A
    kernel.tuning_range is recorded like any other key: tiles follow
    from shapes, so no kernel reads it."""
    global _config
    if config is None:
        _config = {k: {"enable": True} for k in _config}
        return
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    for key, val in dict(config).items():
        if key not in _config or not isinstance(val, dict):
            continue
        _config[key] = {**_config[key], **val}


def get_config():
    return {k: dict(v) for k, v in _config.items()}
