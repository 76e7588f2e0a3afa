"""The serving tests' oracle: one request decoded alone."""
from __future__ import annotations

import numpy as np


def sequential_tokens(fmt, embed, head, prompt, use_rotary: bool = False,
                      **generate_kw) -> np.ndarray:
    """The tokens ``FusedDecoder.generate`` gives ``prompt`` by itself.

    One decoder a model and flavor, kept on the model: its compiled scan
    serves every prompt a test asks about, where a decoder a call compiled
    its own (half of a parity test's time)."""
    from ..inference.generation import FusedDecoder
    from ..tensor.tensor import Tensor
    decoders = fmt.__dict__.setdefault("_oracle_decoders", {})
    if use_rotary not in decoders:
        decoders[use_rotary] = FusedDecoder(fmt, embed, head,
                                            max_seq_len=128,
                                            use_rotary=use_rotary)
    prompt = np.asarray(prompt, np.int32)
    out = decoders[use_rotary].generate(Tensor(prompt[None]), **generate_kw)
    return np.asarray(out._data)[0, prompt.size:]
