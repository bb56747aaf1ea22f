"""paddle_tpu.nn.moe — Mixture-of-Experts under fixed shapes (ISSUE 20).

Top-k routing with capacity-factor token dropping produces shape-
invariant dispatch/combine masks, expert FFN banks are stored stacked
[E, ...] and shard over the 'ep' mesh axis, and the dispatch/combine
einsums become the expert all-to-all under GSPMD — the whole thing
rides the one-compilation captured train step with zero post-warmup
recompiles despite data-dependent routing. See DESIGN_DECISIONS
"MoE under fixed shapes".

`DroplessMoE` (dropless.py) is the served path's layer: sigmoid router with
a selection bias, no capacity and no drop (rows sorted by expert, group
sizes as data), SwiGLU experts, a shared expert, told which experts it
holds. `MoEMLP` stays the layer the GPT MoE trunk trains through.

The older `incubate.distributed.models.moe` package is the reference-
compat API (per-expert sublayers, fused custom op); this package is the
TPU-native subsystem the SPMD path trains through.
"""
from .gate import (MoEConfigError, TopKGate, moe_capacity,  # noqa: F401
                   validate_moe_config)
from .dropless import DroplessMoE  # noqa: F401
from .layer import MoEMLP  # noqa: F401
from . import metrics  # noqa: F401

__all__ = ["MoEConfigError", "TopKGate", "MoEMLP", "DroplessMoE", "moe_capacity",
           "validate_moe_config", "metrics"]
