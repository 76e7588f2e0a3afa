"""A >=1B-param LLaMA proxy under sharding stage-3.

Modes (combinable flags):
  * default (CPU 8-device mesh): build the 1.26B proxy under
    sharding_degree=8 stage-3 (p_g_os), run ONE tiny train step, and
    assert every parameter and optimizer moment is AT REST 1/8 per
    device — the "stage-3 placement actually works at scale" proof.
    Result: LLAMA1B_cpu_mesh.json (ok=true, 603 tensors, 1.762 GB/dev).
  * --tpu (single real chip): attempt the model single-chip. With AdamW
    the analytic table says state alone is 16.45 GB (> 16 GB v5e HBM) —
    the expected record is the OOM that drives the next fix: pod-slice
    sharding (proven by the default mode) or factored moments.
  * --adafactor: use paddle.optimizer.Adafactor (factored second
    moment) — analytic state ~7 GB, so the --tpu single-chip row is
    expected to FIT. This IS the "next fix" the AdamW OOM drives.

Run:  JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/llama_1b.py
      python tools/llama_1b.py --tpu --adafactor   # through the chip tool

One process drives the chip: --tpu starts no child and fails (non-zero)
when JAX finds no TPU. A step that raises — the AdamW OOM included —
prints its record and ends the run non-zero.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def analytic_table(n_params: int) -> dict:
    """Single-chip at-rest optimizer state, bytes: AdamW multi-precision
    vs Adafactor (the factored-moment fix the AdamW OOM drives)."""
    return {
        "adamw": {
            "params_bf16": 2 * n_params,
            "master_fp32": 4 * n_params,
            "moment1_fp32": 4 * n_params,
            "moment2_fp32": 4 * n_params,
            "state_total_gb": round(14 * n_params / 2 ** 30, 2),
        },
        "adafactor": {
            "params_bf16": 2 * n_params,
            "master_fp32": 4 * n_params,
            "row_col_stats": "~KB per matrix (negligible)",
            "state_total_gb": round(6 * n_params / 2 ** 30, 2),
        },
        "hbm_v5e_gb": 16,
    }


def main():
    tpu = "--tpu" in sys.argv
    if tpu:
        from paddle_tpu.device.chip import require_tpu
        require_tpu()
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel import apply_shardings, shard_batch

    # TinyLlama-1.1B-shaped proxy (h2048 x 22L x 5632ff, 32k vocab)
    c = LlamaConfig(vocab_size=32000, hidden_size=2048, num_layers=22,
                    num_heads=16, intermediate_size=5632, max_position=512)
    n_dev = 1 if tpu else 8
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": n_dev}
    fleet.init(is_collective=True, strategy=strategy)

    t0 = time.time()
    paddle.seed(0)
    model = LlamaForCausalLM(c)
    if tpu:
        model.bfloat16()
    n_params = sum(p.size for p in model.parameters())
    print(f"model built: {n_params / 1e9:.3f}B params "
          f"({time.time() - t0:.0f}s)", file=sys.stderr)
    assert n_params >= 1e9, "proxy must be >= 1B params"
    # --adafactor: the factored-moment config the OOM analysis drives —
    # on the single chip, AdamW state is 16.45 GB (> HBM) but Adafactor
    # state is ~7 GB, so the 1B single-chip row becomes runnable
    if "--adafactor" in sys.argv:
        opt = paddle.optimizer.Adafactor(learning_rate=1e-4,
                                         parameters=model.parameters(),
                                         multi_precision=tpu)
    else:
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     multi_precision=tpu)
    model, opt, _ = group_sharded_parallel(model, opt, level="p_g_os")

    batch, seq = (1, 256) if tpu else (1, 64)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, c.vocab_size, (batch, seq + 1)).astype(np.int32)
    x = paddle.to_tensor(ids[:, :-1])
    y = paddle.to_tensor(ids[:, 1:])

    @paddle.jit.to_static
    def train_step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    record = {"metric": "llama_1b_stage3", "params": n_params,
              "n_devices": n_dev, "batch": batch, "seq": seq,
              "optimizer": ("Adafactor" if "--adafactor" in sys.argv
                            else "AdamW"),
              "analytic_single_chip": analytic_table(n_params)}
    try:
        train_step(x, y)            # slot-creation trace
        apply_shardings()
        x, y = shard_batch(x), shard_batch(y)
        t1 = time.time()
        loss = train_step(x, y)
        val = float(np.asarray(loss._data))
        record["loss"] = val
        if tpu:     # a CPU-mesh step time is not a measurement
            record["step_s"] = round(time.time() - t1, 1)

        # at-rest placement proof: every >=1D param + moment is 1/n_dev
        # per device
        inner = opt._inner if hasattr(opt, "_inner") else opt
        state = [p for p in model.parameters() if p.ndim > 0]
        state += [t for slot in inner._accumulators.values()
                  for t in slot.values() if t.ndim > 0]
        bad, per_dev = 0, 0
        for t in state:
            shards = t._data.addressable_shards
            frac = shards[0].data.size * len({s.device for s in shards}) \
                / t._data.size
            if n_dev > 1 and not (0.99 < frac < 1.01):
                bad += 1
            per_dev += shards[0].data.nbytes
        record["state_tensors"] = len(state)
        record["misplaced"] = bad
        record["per_device_state_gb"] = round(per_dev / 2 ** 30, 3)
        record["ok"] = bool(bad == 0 and np.isfinite(val))
    except Exception as e:
        record["ok"] = False
        record["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        print(json.dumps(record, default=str))
        raise
    print(json.dumps(record, default=str))


if __name__ == "__main__":
    main()
