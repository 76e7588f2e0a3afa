"""kernels: the flash-attention kernels' achieved rate against the chip's
bf16 peak: 100 x (the family's ``attention_flops`` a data token x the data
tokens of the traced steps) / (the device self time of the events named
``*flash_attention*`` over those steps x chips x the peak bf16 FLOP/s of the
benchmark's own table). Compute-bound: at head 128 a 1024 x 1024 tile does
0.5 GFLOP on 0.8 MB of operands.

The FLOPs count the ALLOWED entries only and ONE forward (two products) with
its backward (five); the seconds also hold the forward that ``recompute``
replays and the masked entries of the tiles the kernels visit. So the share
reads LOW, never over 100: it is what the attention the model asks for gets
of the chip, not the kernels' MXU occupancy. Nothing without a kernel
reduction by name in ``obs`` (runner ``train_blockdiff``) or without a
flash kernel in the trace."""


def read(obs):
    kernels, t = obs.get("kernels"), obs.get("train") or {}
    if not kernels or not t.get("attention_flops_per_token"):
        return None
    seconds = sum(s for name, s in kernels.items()
                  if "flash_attention" in name)
    if not seconds:
        return None
    return 100.0 * t["attention_flops_per_token"] * t["traced_tokens"] / (
        seconds * t["chips"] * t["peak_bf16_flops"])
