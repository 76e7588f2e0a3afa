"""What PR 32 adds to the benchmark: the family's FLOPs a data token against
a brute-force count from the dense mask, the four new readers on hand-made
counters and kernel times, the kernel reduction by name on a hand-made
trace, the runner's comparison on the float8 control and on the program at
the tiny size, the new manifest entries, and the runner end to end at a tiny
size on the CPU (the device check is patched HERE)."""
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, run
from benchmark.layer_metrics import (attn_roofline_pct,
                                     attn_tiles_visited_pct,
                                     flash_mask_kernel_pct,
                                     masked_tokens_pct)
from benchmark.models import sdar_train as family
from benchmark.reference import sdar as ref
from benchmark.runners import train_blockdiff, train_topk

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "sdar_30b.blockdiff_8k"
TINY = os.path.join(HERE, "data", "sdar", "BENCHMARK.json")


def _cell():
    return harness.Cell(os.path.join(harness.REPO, "BENCHMARK.json"), CELL)


@pytest.mark.parametrize("seq,b", [(32, 4), (64, 32), (30, 4), (37, 5)])
def test_allowed_entries_against_the_dense_mask(seq, b):
    dense = np.asarray(ref.allowed(np.arange(2 * seq), seq, b))
    assert family.allowed_entries(seq, b) == dense.sum()
    if seq % b == 0:
        assert dense.sum() == seq * (seq + b)


def test_flops_against_a_brute_force_count():
    """Small ``L``, every term written out; the attention terms from the
    dense mask's own count of allowed entries."""
    cfg = dict(_cell().config, num_hidden_layers=3, padded_vocab_size=1000,
               block_length=4)
    seq = 64
    e, h, g, d, x, f = 2048, 32, 4, 128, 128, 768
    entries = int(np.asarray(ref.allowed(np.arange(2 * seq), seq, 4)).sum())
    # a position: q, k, v, o, the router, one expected expert of three
    # matrices (8 chosen x 16 held / 128)
    position = e * h * d + 2 * e * g * d + h * d * e + e * x + 1.0 * 3 * e * f
    matrices = 3 * 2 * position + e * 1000      # two positions; head once
    per_entry_fwd = 2 * 2 * d * h               # Q K^T and P V
    want = 6 * matrices + 3 * 3 * per_entry_fwd * entries / seq
    assert family.flops_per_token(cfg, seq) == pytest.approx(want)
    assert family.attention_flops(cfg, seq) == pytest.approx(
        3 * entries / seq * 7 * 2 * d * h)


def test_flops_of_the_cell():
    cfg = _cell().config
    # 4.37 GFLOP a data token, 55% of it attention under the mask
    total = family.flops_per_token(cfg, 8192)
    attention = 12 * 6 * 8196 * 128 * 32
    assert total == pytest.approx(4.37e9, rel=2e-3)
    assert attention / total == pytest.approx(0.553, abs=2e-3)
    assert family.attention_flops(cfg, 8192) == pytest.approx(
        attention * 7 / 6)
    # the parameters this rank holds: 645.6 M
    e = 2048
    layer = (2 * e * 4096 + 2 * e * 512 + 2 * 128 + 2 * e + e * 128
             + 16 * 3 * e * 768)
    assert 6 * layer + e + 2 * e * 18992 == pytest.approx(645.6e6, rel=1e-3)


def test_counter_readers(monkeypatch):
    from paddle_tpu.inference import telemetry
    from paddle_tpu.models import sdar
    monkeypatch.setattr(telemetry, "_runtime_counters", {})
    assert flash_mask_kernel_pct.read({}) is None
    assert attn_tiles_visited_pct.read({}) is None
    telemetry.runtime_counter("paddle_flash_mask_kernel_traces_total", 3)
    assert flash_mask_kernel_pct.read({}) == 100.0
    telemetry.runtime_counter("paddle_flash_mask_composite_traces_total", 1)
    assert flash_mask_kernel_pct.read({}) == 75.0
    telemetry.runtime_counter("paddle_flash_tiles_total", 256)
    telemetry.runtime_counter("paddle_flash_tiles_visited_total", 80)
    assert attn_tiles_visited_pct.read({}) == 31.25
    monkeypatch.setattr(sdar, "noise_stats",
                        lambda: {"tokens": 0, "masked": 0})
    assert masked_tokens_pct.read({}) is None
    monkeypatch.setattr(sdar, "noise_stats",
                        lambda: {"tokens": 8192, "masked": 4301})
    assert masked_tokens_pct.read({}) == pytest.approx(52.5, abs=0.01)


def test_kernel_reduction_by_name_and_the_roofline_reader():
    def call(name, shape="bf16[1,32,16384,128]"):
        return (f"%{name} = {shape} custom-call(bf16[1] %p), "
                'custom_call_target="tpu_custom_call"')
    ms = 1_000_000
    events = [
        ("%while.3 = (s32[]) while(...)", 0, 40 * ms),
        (call("flash_attention_fwd.1"), 1 * ms, 10 * ms),
        (call("flash_attention_fwd.2"), 12 * ms, 10 * ms),
        (call("transpose_jvp_flash_attention_bwd_dkv__.1",
              "(f32[1,32,16384,128], f32[1,32,16384,128])"), 23 * ms, 8 * ms),
        (call("ragged-dot-none.7", "f32[32768,1536]"), 32 * ms, 2 * ms),
        ("%fusion.9 = f32[8] fusion(...)", 35 * ms, 3 * ms),
    ]
    trace = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": events},
                   {"name": "Steps", "events": [("1", 0, 40 * ms)]}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": []}]}]}
    kernels = train_blockdiff.kernel_seconds(trace)
    assert kernels == pytest.approx({
        "flash_attention_fwd": 0.020,
        "transpose_jvp_flash_attention_bwd_dkv__": 0.008,
        "ragged-dot-none": 0.002})
    obs = {"kernels": kernels, "train": {
        "attention_flops_per_token": 2.0e9, "traced_tokens": 1000,
        "chips": 1, "peak_bf16_flops": 1.0e14}}
    # 2e12 FLOP in 0.028 s of flash kernels = 71.4 TFLOP/s of 100
    assert attn_roofline_pct.read(obs) == pytest.approx(100 * 2e12 / 2.8e12)
    # a run of another runner, a trace without flash kernels
    assert attn_roofline_pct.read({"train": obs["train"]}) is None
    assert attn_roofline_pct.read({"kernels": {"ragged-dot-none": 1.0},
                                   "train": obs["train"]}) is None
    assert attn_roofline_pct.read({"kernels": kernels, "train": {}}) is None


def test_comparison_holds_every_token_tighter_than_train_topk():
    rng = np.random.default_rng(0)
    want = rng.standard_normal((1, 500, 64)).astype(np.float32)
    scale = np.abs(want).max()
    got = want.copy()
    got[0, :4, 3] += 0.07 * scale       # a few tokens past 0.05: a flip
    assert train_blockdiff.compare(got, want, 10.0, 10.0)[0]
    got[0, 0, 3] += 0.03 * scale        # one token at 0.1: past 0.08
    assert train_topk.compare(got, want, 10.0, 10.0)[0]
    ok, r = train_blockdiff.compare(got, want, 10.0, 10.0)
    assert not ok and r["worst"] == pytest.approx(0.1 * scale, rel=1e-5)
    assert not train_blockdiff.compare(want, want, 10.03, 10.0)[0]


def test_the_float8_control_is_refused_and_the_program_is_not(capsys):
    """The two readings every limit of ``compare`` lies between, at the tiny
    size: the program (bf16) passes, the reference with its weights and
    layer inputs rounded to float8 in the program's place does not; on the
    same weights, sequences and draw of the noise."""
    cell = harness.Cell(TINY, "tiny_sdar.blockdiff")
    for seed in (3, 2 ** 31 + 4):
        assert not train_blockdiff.control(cell, seed)
        model, _ = family.build(cell.config, seed)
        x, y = train_topk.check_sequences(cell, seed)
        assert train_blockdiff.check(model, family, cell.config, x, y, seed)
    out = capsys.readouterr().out
    assert out.count("NOT OK") == 2 and out.count("; ok in") == 2
    # the draw is the seed's, the same in every call, by the schedule
    masked, t = train_blockdiff.noise(cell.config, 3, 2, 128)
    again = train_blockdiff.noise(cell.config, 3, 2, 128)
    assert (masked == again[0]).all() and (t == again[1]).all()
    assert masked.shape == (2, 128) and t.shape == (2, 32)
    assert t.min() >= 0.05 and t.max() < 1.0 and 0.3 < masked.mean() < 0.75


def test_new_manifest_entries():
    m = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    cfg = next(c for c in m["configs"]
               if c["name"] == "sdar_30b_a3b_train_ep8")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"] and len(cfg["why"]) <= 200
    w = next(w for w in m["workloads"] if w["name"] == CELL)
    assert w["chips"] == 1 and len(w["why"]) <= 200
    assert w == m["workloads"][-1] and cfg == m["configs"][-1]
    tok_s = next(e for e in m["end_to_end"] if e["name"] == "train_tok_s")
    assert tok_s["workloads"] == ["gpt2_124m.pretrain",
                                  "qwen3next_80b.pretrain_8k", CELL]
    mine = [p for p in m["per_layer"] if p.get("workloads") == [CELL]]
    assert [p["name"] for p in mine] == [n + ".sdar" for n in (
        "mfu_pct", "device_idle_pct", "pallas_share_pct",
        "to_static_call_ms", "to_static_dispatch_ms", "to_static_guard_ms",
        "to_static_compiles", "to_static_donated_pct",
        "moe_load_max_over_mean", "moe_rows_padded_pct", "moe_dropped_pairs",
        "flash_mask_kernel_pct", "attn_roofline_pct",
        "attn_tiles_visited_pct", "masked_tokens_pct")]
    assert mine == m["per_layer"][-len(mine):]      # appended, in one piece
    for p in mine:      # every one has its reader, found by name
        harness.load_part("layer_metrics", p["name"].split(".")[0])
    assert all(p["moves"] == "train_tok_s" for p in mine)
    # the file as it is run: the published widths, the cut, the deployment
    c = _cell()
    catalog = {"attention_bias": False, "decoder_sparse_step": 1,
               "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 6144, "max_position_embeddings": 32768,
               "max_window_layers": 48, "mlp_only_layers": [],
               "model_type": "sdar_moe", "moe_intermediate_size": 768,
               "norm_topk_prob": True, "num_attention_heads": 32,
               "num_experts": 128, "num_experts_per_tok": 8,
               "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
               "rope_scaling": None, "rope_theta": 1000000,
               "sliding_window": None, "tie_word_embeddings": False,
               "use_sliding_window": False}
    assert {k: c.config[k] for k in catalog} == catalog
    assert (c.config["num_hidden_layers"], c.config["num_experts_held"],
            c.config["vocab_size"]) == (6, 16, 18992)
    assert c.config["published"] == {"num_hidden_layers": 48,
                                     "num_experts": 128,
                                     "vocab_size": 151936}
    assert {"block_length", "noise_schedule", "noise_eps", "label_shift",
            "mask_token_id", "initializer_range"} <= set(c.config["assumed"])
    assert (c.config["block_length"], c.config["noise_eps"],
            c.config["mask_token_id"]) == (4, 0.05, 18991)
    assert "8" in c.config["deployment"]
    assert (c.traffic["batch"], c.traffic["seq"]) == (1, 8192)


def test_runner_end_to_end_at_a_tiny_size(monkeypatch, tmp_path, capsys):
    import jax
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setitem(harness.PEAKS, jax.devices()[0].device_kind,
                        {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    seed = 2 ** 31 + 9
    run.main(["--workload", "tiny_sdar.blockdiff", "--seed", str(seed),
              "--seconds", "3", "--manifest", TINY],
             t_start=time.monotonic())
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert "check: eval-mode program vs float32 reference" in out
    assert "; ok in" in out             # the comparison itself passed
    assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
    assert line["attempted"] > 2 and line["failed"] == 0
    # off the chip the composite runs: no tpu_custom_call in the lowered
    # step, so the run is, rightly, not ``correct``
    assert line["correct"] is False
    # the per-layer line of a traced run needs a device plane, which the
    # CPU has not: the readers on what that run left in the program
    layer = {k: v["value"] for k, v in harness.read_layer_metrics(
        harness.Cell(TINY, "tiny_sdar.blockdiff"),
        {"trace": None}).items()}
    assert layer["flash_mask_kernel_pct.sdar"] == 0.0   # the CPU's composite
    assert 30 < layer["masked_tokens_pct.sdar"] < 75
    assert layer["to_static_compiles.sdar"] >= 2
    assert layer["moe_dropped_pairs.sdar"] >= 0
    assert "attn_roofline_pct.sdar" not in layer        # no kernel times
    assert "attn_tiles_visited_pct.sdar" not in layer or \
        0 < layer["attn_tiles_visited_pct.sdar"] <= 100
