"""Logical-axis -> mesh-axis rules. The rule set is the primary perf-hillclimb lever:
EXPERIMENTS.md §Perf iterates on these tables.

Logical axes used by the model zoo:
  params: 'embed' (d_model / reduction dim), 'heads' (fused q heads), 'kv' (fused kv),
          'mlp' (d_ff), 'vocab', 'expert', 'expert_in', 'expert_mlp', 'lora', 'conv',
          'inner' (xlstm/ssm inner width), 'ssm_heads' (Mamba-2 per-head),
          'layers' (scanned stack)
  acts:   'act_batch', 'act_seq', 'act_embed', 'act_heads', 'act_kv_seq', 'act_expert',
          'act_vocab'
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# rule tables: logical axis -> mesh axis (or tuple of mesh axes) or None
# ---------------------------------------------------------------------------

# Paper-faithful / baseline distribution: FSDP over 'data', TP over 'model',
# pure DP over 'pod'.
TRAIN_RULES = {
    "embed": "data",          # weights: reduction dim sharded over data (ZeRO-3 style)
    "heads": "model",
    "kv": "model",
    "mlp": "model",
    "vocab": "model",
    "expert": "model",        # only when divisible; configs with E % 16 != 0 use None
    "expert_in": "data",
    "expert_mlp": None,
    "lora": None,
    "inner": "model",
    "inner_in": "data",
    "ssm_heads": "model",     # per-head leaves of the Mamba-2 mixer (dt, A, D)
    "conv": None,
    "layers": None,
    "act_batch": ("pod", "data"),
    "act_moe_batch": ("pod", "data"),
    "act_rnn_batch": ("pod", "data"),
    "act_seq": None,
    "act_embed": None,
    "act_heads": "model",
    "act_mlp": "model",
    "act_kv_seq": "model",    # decode cache sequence dim (flash-decoding split)
    "act_expert": "model",
    "act_vocab": "model",
}

# Serving: params sharded over 'model' only (no per-layer weight all-gathers on the
# latency path); batch over ('pod','data'); cache seq over 'model'.
DECODE_RULES = dict(TRAIN_RULES)
DECODE_RULES.update({"embed": None, "expert_in": None, "inner_in": None})

# Arctic-class models: params don't fit 'model'-only at decode -> both axes.
DECODE_FSDP_RULES = dict(TRAIN_RULES)

# Beyond-paper optimized TRAIN rules (§Perf iteration 1): ZeRO-3 style.
# Activations are sharded over BOTH mesh axes on the batch dim and weights are
# all-gathered per scanned layer — converting the Megatron activation
# all-reduces (O(B*S*d) per layer) into weight all-gathers (O(P_layer)), an
# 8-20x collective-byte reduction at train_4k scale (see EXPERIMENTS.md §Perf).
ZERO3_TRAIN_RULES = dict(TRAIN_RULES)
ZERO3_TRAIN_RULES.update({
    "act_batch": ("data", "model"),
    "act_moe_batch": ("pod", "data"),   # EP stays: experts over 'model'
    "act_rnn_batch": ("data", "model"), # recurrence: fully local under shard_map
    "act_heads": None,
    "act_mlp": None,
    "act_vocab": None,
    "act_expert": "model",
})

RULESETS = {"baseline": None, "zero3": ZERO3_TRAIN_RULES}


def rules_for(cfg, mode: str, ruleset: str = "baseline") -> dict:
    if mode == "train" or mode == "prefill":
        if ruleset == "zero3" and mode == "train":
            rules = dict(ZERO3_TRAIN_RULES)
        else:
            rules = dict(TRAIN_RULES)
    else:
        rules = dict(DECODE_FSDP_RULES if cfg.fsdp_decode else DECODE_RULES)
    if cfg.moe is not None and cfg.moe.n_experts % 16 != 0:
        # expert dim not divisible by the model axis: keep expert weights
        # replicated across 'model' (expert_mlp carries the TP instead).
        rules["expert"] = None
        rules["expert_mlp"] = "model"
    # long-context decode with batch 1: spread the cache over both axes
    return rules


def long_context_rules(rules: dict) -> dict:
    r = dict(rules)
    r["act_batch"] = None
    r["act_kv_seq"] = ("data", "model")
    return r


# ---------------------------------------------------------------------------


def _filter(axes, mesh_axes):
    if axes is None:
        return None
    if isinstance(axes, (tuple, list)):
        kept = tuple(a for a in axes if a in mesh_axes)
        return kept if kept else None
    return axes if axes in mesh_axes else None


class ShardingCtx:
    """Resolves logical axes against a concrete mesh. Threaded through the model."""

    def __init__(self, mesh: Optional[Mesh], rules: dict):
        self.mesh = mesh
        self.rules = rules
        self.mesh_axes = tuple(mesh.axis_names) if mesh is not None else ()

    def pspec(self, logical_axes) -> P:
        parts = []
        for ax in logical_axes:
            if ax is None:
                parts.append(None)
            else:
                parts.append(_filter(self.rules.get(ax), self.mesh_axes))
        return P(*parts)

    def sharding(self, logical_axes) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.pspec(logical_axes))

    def act(self, x, *logical_axes):
        """Activation sharding constraint (no-op without a mesh)."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.sharding(logical_axes))

    def param_shardings(self, spec_tree):
        from repro.models.params import ParamSpec, is_spec
        return jax.tree.map(lambda s: self.sharding(s.axes), spec_tree, is_leaf=is_spec)

    def batch_axes(self):
        return _filter(self.rules.get("act_batch"), self.mesh_axes)

    def kv_seq_axes(self):
        ax = _filter(self.rules.get("act_kv_seq"), self.mesh_axes)
        if ax is None:
            return ()
        return ax if isinstance(ax, tuple) else (ax,)
