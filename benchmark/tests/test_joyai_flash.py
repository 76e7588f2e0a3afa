"""What the JoyAI-LLM-Flash cell adds to the benchmark: the family's FLOPs
and the rank's parameters against counts written out by hand (and against
the numbers the configuration file states), the two new readers on
hand-made rows, the new manifest entries (found by name), family against
reference at a tiny size, the float8 control at a tiny size, and runner
``train_topk_mtp`` end to end at a tiny size on the CPU (the device check
is patched HERE)."""
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, run
from benchmark.layer_metrics import (attn_roofline_pct, mla_rope_device_pct,
                                     mtp_device_pct)
from benchmark.models import joyai_flash_train as family
from benchmark.reference import joyai_flash as ref
from benchmark.runners import train_topk, train_topk_mtp

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "joyai_flash_48b.mtp_pretrain_8k"
TINY = os.path.join(HERE, "data", "joyai", "BENCHMARK.json")
MANIFEST = os.path.join(harness.REPO, "BENCHMARK.json")


def _config():
    return harness.Cell(MANIFEST, CELL).config


def _figure(text):
    """The last number written in ``text`` (``"... = 26,347,520"``)."""
    return int(text.rsplit("=", 1)[1].split()[0].replace(",", ""))


def test_flops_and_parameters_against_a_count_by_hand():
    cfg = _config()
    e, h, s = 2048, 32, 8192
    # MLA: W_qa, W_qb (192 a head), W_kva (512 + 64), W_kvb (128 + 128 a
    # head), W_o
    mla = e * 1536 + 1536 * h * 192 + e * 576 + 512 * h * 256 + h * 128 * e
    dense = 3 * e * 7168
    # router, the ungated shared expert, 8 x 16 / 256 = 0.5 routed experts
    moe = e * 256 + 3 * e * 768 + 0.5 * 3 * e * 768
    # 5 layers + the MTP module's MLA; 4 expert layers + the MTP's; W_eh;
    # the head twice
    n = 6 * mla + dense + 5 * moe + 2 * e * e + 2 * e * 16384
    entries = (s + 1) / 2 * h       # causal entries a token, every head
    assert family.attention_flops(cfg, s) == pytest.approx(
        6 * entries * (2 * 192 + 2 * 128 + 3 * 2 * 192 + 2 * 2 * 128))
    want = 6 * n + 6 * entries * 3 * (2 * 192 + 2 * 128)
    assert family.flops_per_token(cfg, s) == pytest.approx(want)
    assert family.kda_rule_flops(cfg, s) == 0
    # 1.13 G forward a token: attention 503 M, the MLA projections 316 M
    assert want / 3 == pytest.approx(1.134e9, rel=1e-3)
    assert 6 * entries * 2 * (192 + 128) == pytest.approx(503.4e6, rel=1e-3)
    assert 6 * 2 * mla == pytest.approx(316.2e6, rel=1e-3)
    # the parameters this rank holds, as the configuration file states them
    norms = 2 * e
    layer = mla + 1536 + 512
    experts = 16 * 3 * e * 768
    held = {"mla": layer,
            "dense_layer": layer + dense + norms,
            "expert_layer": layer + e * 256 + 3 * e * 768 + norms + experts,
            "mtp_module": 2 * e * e + layer + e * 256 + 3 * e * 768
            + norms + experts + 3 * e,
            "embedding_and_head": 2 * e * 16384}
    stated = cfg["state_arithmetic"]
    assert {k: _figure(stated[k]) for k in held} == held
    total = (held["dense_layer"] + 4 * held["expert_layer"]
             + held["mtp_module"] + held["embedding_and_head"] + e)
    assert _figure(stated["total"].split(" parameters")[0]) == total
    assert total * 16 / 1e9 == pytest.approx(10.90, abs=0.005)
    assert "681.4 M" in cfg["deployment"] and "10.90 GB" in \
        cfg["deployment"]


def _rows(*pairs):
    return [{"pass": "fwd", "layer": None, "scope": scope, "category": "",
             "kernel": "", "seconds": seconds} for scope, seconds in pairs]


@pytest.mark.parametrize("reader,inside,beside", [
    (mtp_device_pct, ["JoyAIFlashForCausalLM/mtp/mtp.embed_proj/eh_proj",
                      "JoyAIFlashForCausalLM/mtp/layer/mlp/moe.experts",
                      "JoyAIFlashForCausalLM/mtp/mtp.head_loss/lm_head"],
     ["JoyAIFlashForCausalLM/lm_head", "JoyAIFlashForCausalLM/mtp_x",
      "model/layers/*/mixer/attn.mla"]),
    (mla_rope_device_pct,
     ["JoyAIFlashForCausalLM/model/layers/*/mixer/attn.mla/mla.rope",
      "JoyAIFlashForCausalLM/mtp/layer/mixer/attn.mla/mla.kv_compress/"
      "mla.rope", "model/layers/*/mixer/attn.mla/mla.rope/while"],
     ["model/layers/*/mixer/attn.mla/mla.kv_compress",
      "model/layers/*/mixer/attn.mla/mla.rope_x",
      "model/layers/*/mixer/attn.mla/mla.q_compress"])])
def test_a_scope_reader_on_hand_made_rows(reader, inside, beside):
    rows = _rows(*[(s, 0.01 * (i + 1)) for i, s in enumerate(inside)],
                 *[(s, 0.5) for s in beside])
    obs = {"scopes": {"rows": rows, "busy_s": 2.0, "steps": 4}}
    assert reader.read(obs) == pytest.approx(100 * 0.06 / 2.0)
    # no such rows (another model), no reduction (another runner)
    assert reader.read({"scopes": {"rows": rows[len(inside):],
                                   "busy_s": 2.0}}) is None
    assert reader.read({"trace": None}) is None


def test_attn_roofline_reads_six_latent_attention_layers():
    """``attn_roofline_pct`` (the accepted reader) on this family's count:
    the flash seconds of six MLA layers at the chip's peak read 100 exactly
    when the kernels take the count's time."""
    cfg = _config()
    per_token = family.attention_flops(cfg, 8192)
    seconds = per_token * 65536 / 197e12
    obs = {"kernels": {"jvp_flash_attention_fwd_": seconds / 4,
                       "flash_attention_bwd_onepass": 3 * seconds / 4,
                       "other": 9.0},
           "train": {"attention_flops_per_token": per_token,
                     "traced_tokens": 65536, "chips": 1,
                     "peak_bf16_flops": 197e12}}
    assert attn_roofline_pct.read(obs) == pytest.approx(100.0)


def test_new_manifest_entries_found_by_name():
    m = harness.load_json(MANIFEST)
    cfg = next(c for c in m["configs"]
               if c["name"] == "joyai_llm_flash_48b_train_ep16")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"] and len(cfg["why"]) <= 200
    w = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        cfg["name"], "pretrain_2x8192", 1) and len(w["why"]) <= 200
    tok_s = next(e for e in m["end_to_end"] if e["name"] == "train_tok_s")
    assert CELL in tok_s["workloads"]
    mine = {p["name"]: p for p in m["per_layer"]
            if p.get("workloads") == [CELL]}
    assert set(mine) == {n + ".joyai" for n in (
        "mfu_pct", "device_idle_pct", "pallas_share_pct",
        "to_static_call_ms", "to_static_donated_pct",
        "moe_load_max_over_mean", "moe_rows_padded_pct", "moe_dropped_pairs",
        "replay_kept_pct", "flash_bwd_onepass_pct", "attn_roofline_pct",
        "setup_trace_s", "setup_compile_s", "mtp_device_pct",
        "mla_rope_device_pct")}
    for p in mine.values():     # every one has its reader, found by name
        harness.load_part("layer_metrics", p["name"].split(".")[0])
    for name in ("mtp_device_pct.joyai", "mla_rope_device_pct.joyai"):
        assert (mine[name]["better"], mine[name]["source"],
                mine[name]["moves"]) == ("lower", "device_trace",
                                         "train_tok_s")
    # the file as it is run: every published width, the cut, the deployment
    c = harness.Cell(MANIFEST, CELL)
    published = {"hidden_size": 2048, "intermediate_size": 7168,
                 "q_lora_rank": 1536, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "qk_head_dim": 192, "v_head_dim": 128,
                 "num_attention_heads": 32, "moe_intermediate_size": 768,
                 "n_routed_experts": 256, "num_experts_per_tok": 8,
                 "n_shared_experts": 1, "routed_scaling_factor": 2.5,
                 "first_k_dense_replace": 1, "rope_theta": 32000000,
                 "rope_interleave": True, "rope_scaling": None,
                 "scoring_func": "sigmoid", "topk_method": "noaux_tc",
                 "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-6}
    assert {k: c.config[k] for k in published} == published
    assert (c.config["num_hidden_layers"], c.config["num_experts_held"],
            c.config["vocab_size"]) == (5, 16, 16384)
    assert c.config["published"] == {"num_hidden_layers": 40,
                                     "num_experts_held": 256,
                                     "vocab_size": 129280}
    assert "16" in c.config["deployment"]
    assert (c.traffic["batch"], c.traffic["seq"],
            c.traffic["check_sequences"]) == (2, 8192, 2)


def test_family_against_the_reference_at_a_tiny_size():
    """Both heads' logits in the layout ``train_topk.compare`` reads, and
    ``L_main + 0.3 L_MTP``, float32 program against float32 reference."""
    import paddle_tpu as paddle
    cell = harness.Cell(TINY, "tiny_joyai.pretrain")
    model, _ = family.build(cell.config, 7)
    model.float()
    x, y = train_topk.check_sequences(cell, 7)
    model.eval()
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(x))._data, np.float32)
        got_loss = float(model(paddle.to_tensor(x),
                               labels=paddle.to_tensor(y))._data)
    w = family.reference_weights(model)
    want, want_loss = train_topk.reference(ref, w, x, y)
    assert got.shape == want.shape == (2, 2 * 128 - 1, 256)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    assert abs(got_loss - want_loss) <= 1e-5 * want_loss


def test_the_float8_control_is_refused_and_the_bf16_program_is_not(capsys):
    cell = harness.Cell(TINY, "tiny_joyai.pretrain")
    for seed in (3, 2 ** 31 + 4):
        with train_topk_mtp.limits():
            assert not train_topk.control(cell, seed)
            model, _ = family.build(cell.config, seed)
            assert train_topk.check(model, family, cell.config,
                                    *train_topk.check_sequences(cell, seed))
    out = capsys.readouterr().out
    assert out.count("NOT OK") == 2 and out.count("; ok in") == 2
    assert out.count("check by head: main rows: share ") == 4


@pytest.fixture
def on_the_cpu(monkeypatch, tmp_path):
    import jax
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setitem(harness.PEAKS, jax.devices()[0].device_kind,
                        {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("TMPDIR", str(tmp_path))


def test_runner_end_to_end_at_a_tiny_size(on_the_cpu, capsys):
    run.main(["--workload", "tiny_joyai.pretrain", "--seed",
              str(2 ** 31 + 9), "--seconds", "2", "--manifest", TINY],
             t_start=time.monotonic())
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert "check: eval-mode program vs float32 reference" in out
    assert "; ok in" in out             # the comparison itself passed
    assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
    assert line["attempted"] > 2 and line["failed"] == 0
    # off the chip the flash kernel is interpreted: not ``correct``
    assert line["correct"] is False
    layer = {k: v["value"] for k, v in harness.read_layer_metrics(
        harness.Cell(TINY, "tiny_joyai.pretrain"), {"trace": None}).items()}
    assert layer["to_static_donated_pct.joyai"] == 100
    assert layer["moe_load_max_over_mean.joyai"] >= 1
    assert "mtp_device_pct.joyai" not in layer      # no trace to read
