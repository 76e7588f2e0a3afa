"""paddle.incubate.distributed.models.moe parity surface."""
from .gate import BaseGate, NaiveGate, GShardGate, SwitchGate
from .moe_layer import MoELayer, ExpertMLP
from .dropless import DroplessMoELayer, routing_stats
from .grad_clip import ClipGradForMOEByGlobalNorm
from .utils import (number_count, limit_by_capacity,
                    prune_gate_by_capacity, random_routing,
                    global_scatter, global_gather)
