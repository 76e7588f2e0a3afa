"""Decode micro-bench: GPT-2-124M-shaped FusedMultiTransformer, compiled
multi-layer KV-cache decode (FusedDecoder) tokens/s on one chip.

Runs on a TPU or not at all (no CPU fallback); through the chip tool:
    python bench_decode.py
Prints ONE JSON line {"metric", "value", "unit", ...}.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import paddle_tpu as paddle
    from paddle_tpu.device.chip import (device_record, require_tpu,
                                        use_compile_cache)
    dev = require_tpu()
    use_compile_cache()
    from paddle_tpu.incubate.nn import FusedMultiTransformer
    from paddle_tpu.inference.generation import FusedDecoder
    from paddle_tpu.nn.layer.common import Embedding, Linear

    E, H, FF, L, V = 768, 12, 3072, 12, 50304
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    smax = int(os.environ.get("BENCH_SMAX", "1024"))
    new_tokens = int(os.environ.get("BENCH_TOKENS", "64"))

    paddle.seed(0)
    embed = Embedding(V, E)
    fmt = FusedMultiTransformer(E, H, FF, num_layers=L, normalize_before=True)
    head = Linear(E, V, bias_attr=False)
    for lay in (embed, fmt, head):
        lay.bfloat16()
    fmt.eval()

    plen = int(os.environ.get("BENCH_PROMPT", "16"))
    # a BENCH_PROMPT longer than the ring must grow the ring, not
    # assert inside generate. FusedDecoder itself rounds max_seq_len up
    # to a 128-multiple (stacked-kernel tiling rule); mirror that here so
    # the record's max_seq is the ACTUAL ring size, not the requested one
    smax = max(smax, plen + new_tokens)
    smax = -(-smax // 128) * 128
    dec = FusedDecoder(fmt, embed, head, max_seq_len=smax)
    prompt = np.random.RandomState(0).randint(
        1, V, (batch, plen)).astype(np.int32)
    # BENCH_BEAMS=K times cache-backed beam search instead of greedy
    # (beams share the prefill cache; per-step reorder is one compiled
    # gather — the serving-side beam mode, r5 verdict #4 ratchet row)
    beams = int(os.environ.get("BENCH_BEAMS", "0"))
    gen_kw = dict(num_beams=beams) if beams > 1 else {}

    # warm with the SAME token count as the timed run: the chunked-scan
    # decode compiles one variant per power-of-two chunk size, and a
    # different count in warmup would leave variants to compile inside the
    # timed region. A compile failure ends the run: no dense-path retry.
    out = dec.generate(paddle.to_tensor(prompt),
                       max_new_tokens=new_tokens, **gen_kw)
    float(np.asarray(out._data).sum())

    t0 = time.perf_counter()
    out = dec.generate(paddle.to_tensor(prompt),
                       max_new_tokens=new_tokens, **gen_kw)
    float(np.asarray(out._data).sum())
    dt = time.perf_counter() - t0
    toks = batch * new_tokens * max(beams, 1)
    record = {
        "metric": "fused_decode_tokens_per_sec",
        "value": round(toks / dt, 2),
        "unit": "tokens/s",
        "batch": batch, "new_tokens": new_tokens, "max_seq": smax,
        "prompt_len": plen,
        "layers": L, "hidden": E,
        "device": device_record(dev),
        # provenance: int8-cache rows must never be silently compared
        # against fp-cache rows
        "cache_mode": ("int8" if os.environ.get(
            "PADDLE_TPU_DECODE_INT8_CACHE") == "1" else "fp"),
        "weight_mode": ("int8" if os.environ.get(
            "PADDLE_TPU_DECODE_INT8_WEIGHTS") == "1" else "fp"),
        "head_mode": ("int8" if os.environ.get(
            "PADDLE_TPU_DECODE_INT8_HEAD") == "1" else "fp"),
        # the path the compiled decode step TOOK, recorded while it
        # traced (FusedDecoder.step_paths) — not inferred from env vars
        "attention_path": dec.step_paths(),
        "num_beams": max(beams, 1),
        "prefill_mode": ("bulk" if os.environ.get(
            "PADDLE_TPU_BULK_PREFILL") == "1" else "scan"),
    }
    print(json.dumps(record))


if __name__ == "__main__":
    main()
