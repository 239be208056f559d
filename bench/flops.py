"""Model FLOPs of the dense decoder the configurations describe, counted
from their shapes: the operations a forward (and backward) pass requires,
with recomputation left out, causal attention counted over the keys each
query may see, and the output head counted where logits are needed."""
from __future__ import annotations

import json
from pathlib import Path


def layer_matmul_params(config):
    d, f = config["hidden_size"], config["intermediate_size"]
    qd = config["num_attention_heads"] * config["head_dim"]
    kvd = config["num_key_value_heads"] * config["head_dim"]
    return d * qd + 2 * d * kvd + qd * d + 3 * d * f


def head_params(config):
    return config["hidden_size"] * config["vocab_size"]


def attention_flops(config, n_queries, first_pos=0):
    """Forward FLOPs of scores and values for queries at positions
    first_pos .. first_pos + n_queries - 1, each over itself and all
    earlier positions, summed over layers."""
    qd = config["num_attention_heads"] * config["head_dim"]
    keys = n_queries * first_pos + n_queries * (n_queries + 1) // 2
    return 4 * qd * keys * config["num_hidden_layers"]


def train_step_flops(config, batch, seq_len):
    """Forward + backward (3x forward) of one training step."""
    L = config["num_hidden_layers"]
    tokens = batch * seq_len
    fwd = 2 * tokens * (L * layer_matmul_params(config) + head_params(config))
    fwd += batch * attention_flops(config, seq_len)
    return 3 * fwd


def prefill_flops(config, prompt_len):
    """A prompt's forward pass, with logits at its last position only."""
    L = config["num_hidden_layers"]
    return (2 * prompt_len * L * layer_matmul_params(config)
            + 2 * head_params(config) + attention_flops(config, prompt_len))


def decode_flops(config, pos):
    """One token decoded at position ``pos`` (attending to pos + 1 keys)."""
    L = config["num_hidden_layers"]
    return (2 * (L * layer_matmul_params(config) + head_params(config))
            + attention_flops(config, 1, first_pos=pos))


def peak_flops(device_kind):
    """Peak bf16 FLOP/s of one chip; an unknown device is an error."""
    table = json.loads((Path(__file__).resolve().parent / "peaks.json")
                       .read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]["bf16_flops_per_s"]
