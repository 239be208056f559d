"""The dense decoder of the ``granite`` configurations: how a configuration
file maps onto the program, the weights' layout, the plain reference and
the model FLOPs.

The program runs a Llama-style block at Granite's widths: RMSNorm, rotary
positions with the file's ``rope_theta``, grouped-query causal softmax
attention, SwiGLU, one stacked segment of layers.  The reference here is
that block, written in ``jax.numpy`` in float32 with every matrix product
at ``Precision.HIGHEST``; ``quant=True`` rounds every product's operands to
float8 e4m3 (``reference.fp8``), the control.  Nothing here imports the
program but ``program_config``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import NEG, _mm, _rmsnorm

#: ``jax.named_scope`` names of this block beyond the shared ones
SCOPES = ()


def program_config(config):
    """The program's ``ModelConfig`` for a configuration file: the preset
    the file names, with every size the file states, checked against the
    settings the file states and the program does not take as sizes."""
    from repro.configs import get_config
    base = get_config(config["program_arch"])
    cfg = dataclasses.replace(
        base, n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        vocab_size=config["vocab_size"])
    want = {"rope_theta": config["rope_theta"], "norm_eps": config["rms_norm_eps"],
            "tie_embeddings": config["tie_word_embeddings"],
            "qkv_bias": config["attention_bias"],
            "param_dtype": config["torch_dtype"],
            "compute_dtype": config["torch_dtype"],
            "opt_state_dtype": config["optimizer_state_dtype"],
            "padded_vocab": config["padded_vocab"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"the program's {cfg.name} differs from the "
                         f"configuration file: {bad}")
    return cfg


# ---------------------------------------------------------------------------
# weights: the program's parameter tree for the dense decoder
# ---------------------------------------------------------------------------

def leaf_specs(config):
    """[(path, shape, init, scale)] in the program's flatten order: embed,
    final_norm, head, then one stacked segment of layers (attn wk wo wq wv,
    ffn wg wi wo, ln1, ln2); ``weights.make`` draws leaf i from
    ``fold_in(key, i)``."""
    d, f = config["hidden_size"], config["intermediate_size"]
    H, K, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"])
    L, Vp = config["num_hidden_layers"], config["padded_vocab"]

    def fan(n):
        return np.float64(1.0) / np.sqrt(n)

    return [
        ("embed", (Vp, d), "embed", 0.02),
        ("final_norm", (d,), "ones", None),
        ("head", (d, Vp), "normal", fan(d)),
        ("segments.0.attn.wk", (L, d, K * hd), "normal", fan(d)),
        ("segments.0.attn.wo", (L, H * hd, d), "normal", fan(H * hd)),
        ("segments.0.attn.wq", (L, d, H * hd), "normal", fan(d)),
        ("segments.0.attn.wv", (L, d, K * hd), "normal", fan(d)),
        ("segments.0.ffn.wg", (L, d, f), "normal", fan(d)),
        ("segments.0.ffn.wi", (L, d, f), "normal", fan(d)),
        ("segments.0.ffn.wo", (L, f, d), "normal", fan(f)),
        ("segments.0.ln1", (L, d), "ones", None),
        ("segments.0.ln2", (L, d), "ones", None),
    ]


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rope(x, theta):
    """x: [B, S, n, hd]; rotate-half convention over the last axis."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(config, p, x, quant=False):
    """One decoder layer over x: [B, S, d] float32; p holds this layer's
    float32 weights {wq, wk, wv, wo, wg, wi, wo2, ln1, ln2}."""
    B, S, _ = x.shape
    H, K, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"])
    eps = config["rms_norm_eps"]
    h = _rmsnorm(x, p["ln1"], eps)
    q = _mm("bsd,df->bsf", h, p["wq"], quant).reshape(B, S, H, hd)
    k = _mm("bsd,df->bsf", h, p["wk"], quant).reshape(B, S, K, hd)
    v = _mm("bsd,df->bsf", h, p["wv"], quant).reshape(B, S, K, hd)
    q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
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
    """Layer ``i`` of a ``weights.make`` tree, as float32."""
    seg = params["segments"][0]
    f = lambda x: x[i].astype(jnp.float32)  # noqa: E731
    return {"wq": f(seg["attn"]["wq"]), "wk": f(seg["attn"]["wk"]),
            "wv": f(seg["attn"]["wv"]), "wo": f(seg["attn"]["wo"]),
            "wg": f(seg["ffn"]["wg"]), "wi": f(seg["ffn"]["wi"]),
            "wo2": f(seg["ffn"]["wo"]), "ln1": f(seg["ln1"]),
            "ln2": f(seg["ln2"])}


def logits(config, params, tokens, quant=False):
    """[B, S] int tokens -> [B, S, padded_vocab] float32 logits."""
    f32 = jnp.float32
    x = jnp.take(params["embed"].astype(f32), tokens, axis=0)
    body = jax.checkpoint(lambda x, i: layer(config, layer_weights(params, i),
                                              x, quant))
    for i in range(config["num_hidden_layers"]):
        x = body(x, i)
    x = _rmsnorm(x, params["final_norm"].astype(f32), config["rms_norm_eps"])
    return _mm("bsd,dv->bsv", x, params["head"].astype(f32), quant)


def serve_logits(config, params, seqs, length, quant=False):
    """Reference logits for each sequence (prompt + served tokens but the
    last), layer by layer over the batch padded to ``length``.  Returns a
    list of [len(seq), vocab_size] float32 host arrays."""
    f32 = jnp.float32
    toks = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    x = jnp.take(params["embed"], jnp.asarray(toks), axis=0).astype(f32)
    step = jax.jit(lambda p, x: layer(config, p, x, quant))
    for i in range(config["num_hidden_layers"]):
        x = step(layer_weights(params, i), x)
    head = jax.jit(lambda x, w, n: _mm(
        "sd,dv->sv", _rmsnorm(x, n, config["rms_norm_eps"]), w, quant))
    w, n = params["head"].astype(f32), params["final_norm"].astype(f32)
    V = config["vocab_size"]
    return [np.asarray(head(x[i], w, n))[:len(s), :V]
            for i, s in enumerate(seqs)]


# ---------------------------------------------------------------------------
# model FLOPs, counted from the shapes: the operations a forward (and
# backward) pass requires, with recomputation left out, causal attention
# counted over the keys each query may see, and the output head counted
# where logits are needed
# ---------------------------------------------------------------------------

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
