"""A second architecture for the harness's own tests: the dense block of
``granite`` with a bias on each query, key and value projection (the
configuration's ``attention_bias``, the program's ``qkv_bias``), which adds
the leaves ``bk``, ``bq``, ``bv`` to each layer.

It lives with the tests.  A test copies it beside ``bench/archs/granite.py``
into a checkout of its own, as a change that adds an architecture would add
it to ``bench/archs/``.  Its biases are drawn from the seed, where the
program starts them at zero, so that the first step's loss already
depends on them.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import common
from bench.reference import NEG, _mm, _rmsnorm

GRANITE = common.arch({"model_type": "granite"})
SCOPES = ()
train_step_flops = GRANITE.train_step_flops
prefill_flops = GRANITE.prefill_flops
decode_flops = GRANITE.decode_flops


def program_config(config):
    """granite's mapping, with ``qkv_bias`` taken from the file."""
    cfg = GRANITE.program_config(dict(config, attention_bias=False))
    return dataclasses.replace(cfg, qkv_bias=config["attention_bias"])


def leaf_specs(config):
    """granite's leaves with the three biases before ``attn.wk``, in the
    program's flatten order."""
    specs = GRANITE.leaf_specs(config)
    at = [s[0] for s in specs].index("segments.0.attn.wk")
    L, hd = config["num_hidden_layers"], config["head_dim"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    bias = [(f"segments.0.attn.{b}", (L, n * hd), "normal", 0.02)
            for b, n in (("bk", K), ("bq", H), ("bv", K))]
    return specs[:at] + bias + specs[at:]


def layer(config, p, x, quant=False):
    """granite's layer with the biases added after the projections."""
    B, S, _ = x.shape
    H, K, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"])
    eps = config["rms_norm_eps"]
    h = _rmsnorm(x, p["ln1"], eps)
    q = (_mm("bsd,df->bsf", h, p["wq"], quant) + p["bq"]).reshape(B, S, H, hd)
    k = (_mm("bsd,df->bsf", h, p["wk"], quant) + p["bk"]).reshape(B, S, K, hd)
    v = (_mm("bsd,df->bsf", h, p["wv"], quant) + p["bv"]).reshape(B, S, K, hd)
    rope = GRANITE._rope
    q, k = rope(q, config["rope_theta"]), rope(k, config["rope_theta"])
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", a, v, quant).reshape(B, S, H * hd)
    x = x + _mm("bsf,fd->bsd", o, p["wo"], quant)
    h = _rmsnorm(x, p["ln2"], eps)
    g = _mm("bsd,df->bsf", h, p["wg"], quant)
    u = _mm("bsd,df->bsf", h, p["wi"], quant)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["wo2"], quant)


def layer_weights(params, i):
    attn = params["segments"][0]["attn"]
    biases = {b: attn[b][i].astype(jnp.float32) for b in ("bq", "bk", "bv")}
    return dict(GRANITE.layer_weights(params, i), **biases)


def logits(config, params, tokens, quant=False):
    f32 = jnp.float32
    x = jnp.take(params["embed"].astype(f32), tokens, axis=0)
    body = jax.checkpoint(lambda x, i: layer(config, layer_weights(params, i),
                                              x, quant))
    for i in range(config["num_hidden_layers"]):
        x = body(x, i)
    x = _rmsnorm(x, params["final_norm"].astype(f32), config["rms_norm_eps"])
    return _mm("bsd,dv->bsv", x, params["head"].astype(f32), quant)


def serve_logits(config, params, seqs, length, quant=False):
    toks = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    lg = logits(config, params, jnp.asarray(toks), quant)
    V = config["vocab_size"]
    return [np.asarray(lg[i])[:len(s), :V] for i, s in enumerate(seqs)]
