"""Compiled multi-layer fused decode vs the model-agnostic generate oracle,
second part (``tests/test_fused_decode.py`` has the model and the first): beam
search against the decode cache, and with a long prompt's prefix split."""
import numpy as np
import pytest

import paddle_tpu as paddle

from paddle_tpu.inference.generation import generate, generate_fused
from test_fused_decode import TinyFusedLM, _prompt


class TestBeamOverCache:
    """r5 (reference: fluid beam_search op + fused_multi_transformer
    cache): beam search runs AGAINST the decode cache — beams share the
    prefill cache, each step's beam reorder is one gather on the
    batch*beam dim inside the compiled step, no prefix re-forward."""

    @pytest.mark.parametrize("seed,toks", [(11, 6), (41, 16), (43, 16)])
    def test_fused_beam_matches_generate(self, seed, toks):
        # 16-token runs matter: a cache-position off-by-one only flips
        # top-k picks once divergence accumulates (review r5 found the
        # t0=prompt+1 bug exactly this way)
        paddle.seed(23 + seed)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=seed)
        ref = generate(m, paddle.to_tensor(ids), max_new_tokens=toks,
                       num_beams=4)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=toks, num_beams=4)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))

    def test_fused_beam_matches_generate_with_eos(self):
        paddle.seed(24)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=13)
        # a mid-vocab eos makes some beams finish early: exercises the
        # finished pool + eos-frozen continuations + trim semantics
        eos = 7
        ref = generate(m, paddle.to_tensor(ids), max_new_tokens=10,
                       num_beams=3, eos_token_id=eos, length_penalty=0.8)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=10, num_beams=3,
                             eos_token_id=eos, length_penalty=0.8)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))

    def test_beam_rejects_sampling(self):
        paddle.seed(25)
        m = TinyFusedLM()
        with pytest.raises(ValueError, match="deterministic"):
            generate_fused(m.fmt, paddle.to_tensor(_prompt()),
                           embed=m.embed, head=m.head, num_beams=2,
                           do_sample=True)


class TestBeamPrefixSplit:
    @pytest.mark.parametrize("int8", [False, True])
    def test_long_prompt_split_reorder_matches_generate(self, int8,
                                                        monkeypatch):
        """r5: with prompt >= 64 the beam reorder only gathers cache
        positions past the shared-prefix split (the prompt region is
        identical across beams — reordering it is a no-op). Token
        parity with the model-agnostic beam must hold through the split
        path, fp and int8."""
        if int8:
            monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_CACHE", "1")
            monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_WEIGHTS", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_DECODE_INT8_CACHE",
                               raising=False)
            monkeypatch.delenv("PADDLE_TPU_DECODE_INT8_WEIGHTS",
                               raising=False)
        paddle.seed(45)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(b=2, s=80, seed=33)   # split = 64
        kw = dict(max_new_tokens=8, num_beams=3, max_seq_len=128)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, **kw)
        # oracle: the model-agnostic beam (no cache, no split machinery)
        for k_ in ("PADDLE_TPU_DECODE_INT8_CACHE",
                   "PADDLE_TPU_DECODE_INT8_WEIGHTS"):
            monkeypatch.delenv(k_, raising=False)
        ref = generate(m, paddle.to_tensor(ids), max_new_tokens=8,
                       num_beams=3)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))
