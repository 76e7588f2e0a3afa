"""MoE layer with expert parallelism.

Parity: python/paddle/incubate/distributed/models/moe/moe_layer.py ::
MoELayer (+ the global_scatter/global_gather CUDA alltoall ops of
paddle/fluid/operators/collective/ and utils count/limit/prune ops).

TPU-native design: experts live as STACKED parameters [E, ...] whose expert
dim is sharded over the data axes of the mesh (expert parallelism rides the
same chips as dp, as ERNIE's Fleet config does). Token dispatch/combine are
dense einsums against the gate's capacity masks — under jit, GSPMD lowers the
sharded einsum into exactly the all-to-all exchange the reference's
global_scatter/global_gather kernels perform, scheduled on ICI.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .....nn import functional as F
from .....nn.initializer import Normal
from .....nn.layer.layers import Layer
from .....tensor.tensor import Parameter, Tensor, apply_op
from .gate import GShardGate, NaiveGate, SwitchGate

__all__ = ["MoELayer", "ExpertMLP"]


class ExpertMLP(Layer):
    """Stacked expert FFN: weights [E, d, h] / [E, h, d], vmapped over E."""

    def __init__(self, num_experts, d_model, d_hidden, activation="gelu"):
        super().__init__()
        init = Normal(0.0, 0.02)
        self.w1 = Parameter(init((num_experts, d_model, d_hidden),
                                 jnp.float32))
        self.b1 = Parameter(jnp.zeros((num_experts, d_hidden), jnp.float32))
        self.w2 = Parameter(init((num_experts, d_hidden, d_model),
                                 jnp.float32))
        self.b2 = Parameter(jnp.zeros((num_experts, d_model), jnp.float32))
        # expert dim sharded over the dp axis (expert parallelism)
        for p in (self.w1, self.b1, self.w2, self.b2):
            p.sharding_spec = P("dp")
            p.is_distributed = True
        self.activation = activation

    def forward(self, expert_inputs):
        """expert_inputs [E, B, C, d] → [E, B, C, d]."""
        act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
               "silu": jax.nn.silu}[self.activation]

        def f(x, w1, b1, w2, b2):
            h = jnp.einsum("ebcd,edh->ebch", x, w1) + b1[:, None, None, :]
            h = act(h)
            return jnp.einsum("ebch,ehd->ebcd", h, w2) + b2[:, None, None, :]
        return apply_op(f, expert_inputs, self.w1, self.b1, self.w2, self.b2)


class MoELayer(Layer):
    """Parity: MoELayer(d_model, experts, gate="gshard", top_k, ...).

    forward(x): [B, S, d] → [B, S, d]; aux (load-balance) loss accumulates on
    self.gate.aux_loss — add `moe.gate.aux_loss * coeff` to the train loss as
    the reference does.
    """

    def __init__(self, d_model, d_hidden=None, num_experts=8, experts=None,
                 gate="gshard", top_k=2, capacity_factor=1.2,
                 group=None, recompute_interval=0, activation="gelu",
                 moe_group=None, mp_group=None, **kw):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        if isinstance(gate, str):
            cls = {"naive": NaiveGate, "gshard": GShardGate,
                   "switch": SwitchGate}[gate]
            if gate == "switch":
                top_k = 1
            self.gate = cls(d_model, num_experts,
                            capacity_factor=capacity_factor)
        else:
            self.gate = gate
        self.experts = experts or ExpertMLP(num_experts, d_model,
                                            d_hidden or 4 * d_model,
                                            activation)

    def forward(self, x):
        combine, dispatch, aux = self.gate(x)  # [B,S,E,C] masks

        def dispatch_fn(xx, dd):
            return jnp.einsum("bsec,bsd->ebcd", dd, xx)
        expert_in = apply_op(dispatch_fn, x, dispatch)
        expert_out = self.experts(expert_in)   # [E,B,C,d]

        def combine_fn(cc, eo):
            return jnp.einsum("bsec,ebcd->bsd", cc, eo)
        out = apply_op(combine_fn, combine, expert_out)
        return out
