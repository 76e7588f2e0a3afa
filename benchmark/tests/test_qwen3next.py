"""What PR 28 adds to the benchmark: the ``moe_*`` readers on hand-made
``routing_stats()`` records, the family's FLOPs a token against a count
written out by hand, the comparison of runner ``train_topk`` on hand-made
logits, the new manifest entries, and the runner end to end at a tiny size
on the CPU (the device check is patched HERE)."""
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, run
from benchmark.layer_metrics import (moe_dropped_pairs,
                                     moe_load_max_over_mean,
                                     moe_rows_padded_pct)
from benchmark.models import qwen3_next_train as family
from benchmark.runners import train, train_topk

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "qwen3next_80b.pretrain_8k"


def _layer(rows, dropped=0, buffer=64, pairs=400):
    return {"pairs": pairs, "pairs_local": sum(rows), "rows_computed": buffer,
            "pairs_dropped": dropped, "experts_held": list(range(len(rows))),
            "rows_per_expert": rows}


def _stats(*layers):
    out = {k: sum(r[k] for r in layers) for k in
           ("pairs", "pairs_local", "rows_computed", "pairs_dropped")}
    out["layers"] = list(layers)
    return out


def test_moe_readers_on_hand_made_records():
    even = _stats(_layer([10, 10, 10, 10]), _layer([5, 5, 5, 5]))
    assert moe_load_max_over_mean.load_ratio(even) == 1.0
    # layer 1: max 30 / mean 15 = 2; layer 2: max 8 / mean 5 = 1.6
    skew = _stats(_layer([30, 10, 10, 10]), _layer([8, 4, 4, 4]))
    assert moe_load_max_over_mean.load_ratio(skew) == pytest.approx(1.8)
    # 128 rows of buffer, 60 + 20 pairs routed here, none dropped
    assert moe_rows_padded_pct.padded_pct(skew) == pytest.approx(
        100 * (128 - 80) / 128)
    # 70 local pairs, 6 past the buffer of 64: every row of it is used
    full = _stats(_layer([40, 30], dropped=6))
    assert moe_rows_padded_pct.padded_pct(full) == 0.0
    # a layer that no token reached yet takes no part in the mean
    assert moe_load_max_over_mean.load_ratio(
        _stats(_layer([0, 0]), _layer([3, 1]))) == 1.5


def test_moe_readers_read_the_program(monkeypatch):
    from paddle_tpu.incubate.distributed.models import moe
    rec = _stats(_layer([30, 10], dropped=2))
    monkeypatch.setattr(moe, "routing_stats", lambda: rec)
    assert moe_dropped_pairs.read({}) == 2
    assert moe_load_max_over_mean.read({}) == 1.5
    assert moe_rows_padded_pct.read({}) == pytest.approx(100 * 26 / 64)
    # a process that built no expert layer, and a program before PR 28
    monkeypatch.setattr(moe, "routing_stats", lambda: _stats())
    assert moe_dropped_pairs.read({}) is None
    monkeypatch.delattr(moe, "routing_stats")
    assert moe_load_max_over_mean.read({}) is None
    assert moe_rows_padded_pct.read({}) is None


def test_flops_per_token_against_a_count_by_hand():
    cfg = harness.Cell(os.path.join(harness.REPO, "BENCHMARK.json"),
                       CELL).config
    e = 2048
    # Gated DeltaNet: in_proj_qkvz, in_proj_ba, out_proj
    linear = e * 12288 + e * 64 + 4096 * e
    # the rule's products a token and value head, chunk 64, d 128:
    # KK^T + QK^T 2 x 2 x 64 x 128, solve 64 x 256, W S + Q S 2 x 2 x 128^2,
    # A V' 2 x 64 x 128, K^T V' 2 x 128^2
    rule = 32 * (32768 + 16384 + 65536 + 16384 + 32768)
    # gated attention: q_proj (query and gate), k_proj + v_proj, o_proj
    full = e * 8192 + 2 * e * 512 + 4096 * e
    # router, shared expert and its gate, 0.625 routed experts
    moe = e * 512 + 3 * e * 512 + e + 0.625 * 3 * e * 512
    n = 3 * linear + full + 4 * moe + e * 18992
    want = 6 * n + 3 * 3 * rule + 12 * 16 * 256 * 8192
    assert family.flops_per_token(cfg, 8192) == pytest.approx(want)
    # 1.15 G in the matrices, 0.40 G in attention, 0.05 G in the rule
    assert want == pytest.approx(1.601e9, rel=1e-3)
    # the parameters this rank holds: 625.7 M, 10.0 GB at 16 B each
    held = (3 * (linear + 8192 * 4 + 32 * 2 + 128) + full + 2 * 256
            + 4 * (e * 512 + 3 * e * 512 + e + 32 * 3 * e * 512)
            + 9 * e + 2 * e * 18992)
    assert held == pytest.approx(625.7e6, rel=1e-3)


def test_comparison_holds_the_body_and_bounds_the_flips():
    rng = np.random.default_rng(0)
    want = rng.standard_normal((2, 500, 64)).astype(np.float32)
    scale = np.abs(want).max()

    def verdict(got, loss=10.0):
        return train_topk.compare(got, want, loss, 10.0)

    ok, r = verdict(want + 0.01 * scale)
    assert ok and r["share"] == 0.0
    # a few tokens moved by a flipped choice: inside both limits
    got = want.copy()
    got[0, :10, 3] += 0.1 * scale
    ok, r = verdict(got)
    assert ok and r["share"] == pytest.approx(0.01)
    # too many tokens past the dense bound
    got[1, :5, 5] += 0.1 * scale
    assert not verdict(got)[0]
    # one token past the bound on every token
    got = want.copy()
    got[0, 0, 0] += 0.3 * scale
    assert not verdict(got)[0]
    # the loss, and a value that is not a number
    assert not verdict(want, loss=10.0 * (1 + 2 * train.LOSS_RTOL))[0]
    got = want.copy()
    got[1, 2, 3] = np.nan
    assert not verdict(got)[0]


def test_the_float8_control_is_refused_and_the_bf16_program_is_not(capsys):
    """The two readings every limit of ``compare`` lies between, at the tiny
    size: the program in bf16 passes, the reference with its weights and
    layer inputs rounded to float8 in the program's place does not."""
    cell = harness.Cell(os.path.join(HERE, "data", "qwen3next",
                                     "BENCHMARK.json"),
                        "tiny_qwen3next.pretrain")
    for seed in (3, 2 ** 31 + 4):
        assert not train_topk.control(cell, seed)
        model, _ = family.build(cell.config, seed)
        assert train_topk.check(model, family, cell.config,
                                *train_topk.check_sequences(cell, seed))
    out = capsys.readouterr().out
    assert out.count("NOT OK") == 2 and out.count("; ok in") == 2


def test_new_manifest_entries():
    m = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    cfg = next(c for c in m["configs"]
               if c["name"] == "qwen3next_80b_a3b_train_ep16")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"] and len(cfg["why"]) <= 200
    w = next(w for w in m["workloads"] if w["name"] == CELL)
    assert w["chips"] == 1 and len(w["why"]) <= 200
    assert w == m["workloads"][-1] and cfg == m["configs"][-1]
    tok_s = next(e for e in m["end_to_end"] if e["name"] == "train_tok_s")
    assert tok_s["workloads"] == ["gpt2_124m.pretrain", CELL]
    mine = [p for p in m["per_layer"] if p.get("workloads") == [CELL]]
    assert {p["name"] for p in mine} >= {
        n + ".qwen3next" for n in (
            "mfu_pct", "device_idle_pct", "pallas_share_pct",
            "to_static_call_ms", "to_static_donated_pct",
            "moe_load_max_over_mean", "moe_rows_padded_pct",
            "moe_dropped_pairs", "to_static_dispatch_ms",
            "to_static_guard_ms", "to_static_compiles")}
    assert mine == m["per_layer"][-len(mine):]      # appended, in one piece
    for p in mine:      # every one has its reader, found by name
        harness.load_part("layer_metrics", p["name"].split(".")[0])
    assert all(p["moves"] == "train_tok_s" for p in mine)
    # the file as it is run: the published widths, the cut, the deployment
    c = harness.Cell(os.path.join(harness.REPO, "BENCHMARK.json"), CELL)
    published = {"hidden_size": 2048, "num_attention_heads": 16,
                 "num_key_value_heads": 2, "head_dim": 256,
                 "linear_num_key_heads": 16, "linear_num_value_heads": 32,
                 "linear_key_head_dim": 128, "linear_value_head_dim": 128,
                 "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
                 "num_experts": 512, "num_experts_per_tok": 10,
                 "shared_expert_intermediate_size": 512,
                 "full_attention_interval": 4}
    assert {k: c.config[k] for k in published} == published
    assert (c.config["num_hidden_layers"], c.config["num_experts_held"],
            c.config["vocab_size"]) == (4, 32, 18992)
    assert "16" in c.config["deployment"]
    assert (c.traffic["batch"], c.traffic["seq"]) == (2, 8192)


def test_runner_end_to_end_at_a_tiny_size(monkeypatch, tmp_path, capsys):
    import jax
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setitem(harness.PEAKS, jax.devices()[0].device_kind,
                        {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    manifest = os.path.join(HERE, "data", "qwen3next", "BENCHMARK.json")
    # the counts are the process's: what earlier tests of it dropped stays
    dropped = (moe_load_max_over_mean.stats() or {"pairs_dropped": 0})[
        "pairs_dropped"]
    compared = []
    monkeypatch.setattr(train_topk, "check", lambda model, fam, cfg, x, y, f=
                        train_topk.check: compared.append(x) or f(
                            model, fam, cfg, x, y))
    run.main(["--workload", "tiny_qwen3next.pretrain", "--seed",
              str(2 ** 31 + 9), "--seconds", "3", "--manifest", manifest],
             t_start=time.monotonic())
    # the control (train_topk's script) draws the sequences the run compared
    np.testing.assert_array_equal(compared[0], train_topk.check_sequences(
        harness.Cell(manifest, "tiny_qwen3next.pretrain"), 2 ** 31 + 9)[0])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert "check: eval-mode program vs float32 reference" in out
    assert "; ok in" in out             # the comparison itself passed
    assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
    assert line["attempted"] > 2 and line["failed"] == 0
    # off the chip the flash kernel is interpreted: no tpu_custom_call in
    # the lowered step, so the run is, rightly, not ``correct``
    assert line["correct"] is False
    # the per-layer line of a traced run needs a device plane, which the
    # CPU has not: the readers on what that run left in the program
    layer = {k: v["value"] for k, v in harness.read_layer_metrics(
        harness.Cell(manifest, "tiny_qwen3next.pretrain"),
        {"trace": None}).items()}
    assert layer["moe_dropped_pairs.qwen3next"] == dropped
    assert layer["to_static_donated_pct.qwen3next"] == 100
    assert layer["to_static_compiles.qwen3next"] >= 2
    assert layer["to_static_dispatch_ms.qwen3next"] > 0
    assert layer["to_static_guard_ms.qwen3next"] > 0
    assert layer["moe_load_max_over_mean.qwen3next"] >= 1
    assert 0 <= layer["moe_rows_padded_pct.qwen3next"] < 100
