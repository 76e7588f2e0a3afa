"""trainer: model-FLOP utilisation, (the family's FLOPs a token, forward and
backward, recomputation not counted) x tokens/s of the traced steps / (chips
x the peak bf16 FLOP/s of the benchmark's own table)."""


def read(obs):
    t = obs.get("train")
    if not t or not t.get("traced_tok_s"):
        return None
    return 100.0 * t["flops_per_token"] * t["traced_tok_s"] / (
        t["chips"] * t["peak_bf16_flops"])
