"""Ablation profile of the FusedDecoder per-token decode cost.

An early round measured ~0.58 s fixed + ~10 ms/token marginal against a
~1 ms/token memory floor; this tool isolates where the marginal cost lives
by timing compiled 64-token decode chunks with pieces swapped out:

  full         — the real chunk scan (attend kernel + cache update + head)
  dense_attend — decode-kernel dispatch gate forced off, so attention
                 runs the dense masked einsum fallback; full vs dense
                 isolates the Pallas decode kernel's share
  two_layer    — same model truncated to 2 layers (isolates per-layer
                 cost linearity: cost should be ~L/6 + fixed)
  short        — same run at tokens/8 new tokens (fixed-vs-marginal
                 split; reported as marginal_ms_per_token)

Run on TPU:  python tools/decode_profile.py
Prints one JSON line per variant to stdout; progress to stderr.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build(layers):
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import FusedMultiTransformer
    from paddle_tpu.inference.generation import FusedDecoder
    from paddle_tpu.nn.layer.common import Embedding, Linear

    E, H, FF, V = 768, 12, 3072, 50304
    paddle.seed(0)
    embed = Embedding(V, E)
    fmt = FusedMultiTransformer(E, H, FF, num_layers=layers,
                                normalize_before=True)
    head = Linear(E, V, bias_attr=False)
    for lay in (embed, fmt, head):
        lay.bfloat16()
    fmt.eval()
    return FusedDecoder(fmt, embed, head, max_seq_len=1024)


def _time_generate(dec, batch=8, tokens=64, prompt_len=16):
    import paddle_tpu as paddle
    prompt = np.random.RandomState(0).randint(
        1, 50000, (batch, prompt_len)).astype(np.int32)
    out = dec.generate(paddle.to_tensor(prompt), max_new_tokens=tokens)
    float(np.asarray(out._data).sum())          # compile + warm
    t0 = time.perf_counter()
    out = dec.generate(paddle.to_tensor(prompt), max_new_tokens=tokens)
    float(np.asarray(out._data).sum())
    return time.perf_counter() - t0


def main():
    from paddle_tpu.device.chip import (device_record, require_tpu,
                                        use_compile_cache)
    dev = require_tpu()
    use_compile_cache()

    tokens = int(os.environ.get("PROF_TOKENS", "64"))
    results = {}

    dec = _build(12)
    results["full"] = _time_generate(dec, tokens=tokens)
    print(f"decode_profile: full {results['full']:.3f}s", file=sys.stderr)

    # attend lives in a closure — ablate at the module level: force the
    # decode-kernel dispatch gate off so the dense masked fallback (einsum
    # over the cache) runs instead; full vs dense isolates the Pallas
    # decode kernel's share.
    from paddle_tpu.ops.pallas import decode_attention as da
    orig_sup = da.is_supported
    da.is_supported = lambda *a, **kw: False
    try:
        dec2 = _build(12)
        results["dense_attend"] = _time_generate(dec2, tokens=tokens)
        print(f"decode_profile: dense_attend {results['dense_attend']:.3f}s",
              file=sys.stderr)
    finally:
        da.is_supported = orig_sup

    dec3 = _build(2)
    results["two_layer"] = _time_generate(dec3, tokens=tokens)
    print(f"decode_profile: two_layer {results['two_layer']:.3f}s",
          file=sys.stderr)

    # fixed-vs-marginal split at this chunk size
    short_n = max(tokens // 8, 1)
    results["short"] = _time_generate(_build(12), tokens=short_n)
    per_tok = (results["full"] - results["short"]) / max(tokens - short_n, 1)
    rec = {
        "metric": "decode_profile",
        "tokens": tokens,
        "full_s": round(results["full"], 4),
        "dense_attend_s": round(results["dense_attend"], 4),
        "two_layer_s": round(results["two_layer"], 4),
        "short8_s": round(results["short"], 4),
        "marginal_ms_per_token": round(per_tok * 1e3, 3),
        "device": device_record(dev),
    }
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
