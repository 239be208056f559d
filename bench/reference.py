"""The plain reference of the configurations the benchmark runs: a dense
decoder block as the configuration file states it (RMSNorm, rotary
positions with the file's ``rope_theta``, grouped-query causal softmax
attention, SwiGLU), written in ``jax.numpy`` in float32 with every matrix
product at ``Precision.HIGHEST``.  It imports nothing of the program and is
given only the configuration file, weights made by ``weights.make`` from
the seed, and the tokens the benchmark sent.

``quant=True`` is the control: the same reference with every matrix
product's operands rounded to float8 e4m3 (per-tensor scale, gradient
passed straight through), the precision below the bfloat16 the
configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30


def fp8(x):
    """Round to float8 e4m3 under a per-tensor scale; identity gradient."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, quant):
    if quant:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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


def lm_loss(config, lg, targets):
    """Mean next-token cross-entropy over the real (unpadded) vocabulary."""
    V = config["vocab_size"]
    lg = jnp.where(jnp.arange(lg.shape[-1]) < V, lg, NEG)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


# ---------------------------------------------------------------------------
# training: three AdamW steps as the configuration states them
# ---------------------------------------------------------------------------

def _loss_and_grad(config, params, batch, quant):
    """Loss and float32 gradient over a batch, one row at a time."""
    f32 = jnp.float32
    p32 = jax.tree.map(lambda x: x.astype(f32), params)

    def row_loss(p, tok, tgt):
        return lm_loss(config, logits(config, p, tok[None], quant), tgt[None])

    def body(acc, row):
        loss, g = jax.value_and_grad(row_loss)(p32, *row)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), f32), jax.tree.map(jnp.zeros_like, p32))
    (loss, g), _ = jax.lax.scan(body, zero, (batch["tokens"], batch["targets"]))
    n = batch["tokens"].shape[0]
    return loss / n, jax.tree.map(lambda x: x / n, g)


def _adamw(opt, params, grads, m, v, step):
    """One AdamW update with global-norm clipping; params keep their
    stored dtype, moments stay float32."""
    f32 = jnp.float32
    warm = max(opt["total_steps"] // 20, 1)
    lr = opt["lr"] * jnp.minimum(step / warm, 1.0)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    t = step + 1.0
    b1, b2 = opt["adam_b1"], opt["adam_b2"]
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)

    def upd(p, mm, vv):
        u = (mm / (1 - b1 ** t)) / (jnp.sqrt(vv / (1 - b2 ** t)) + opt["adam_eps"])
        u = u + opt["weight_decay"] * p.astype(f32)
        return (p.astype(f32) - lr * u).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), m, v, g


def train_step(config, opt, quant=False):
    """The jitted reference step: (params, m, v, batch, step) -> (params,
    m, v, loss, per-leaf norms of the clipped gradient); the moments are
    donated, so that the step fits beside the program's freed state."""
    def step_fn(p, m, v, batch, step):
        loss, g = _loss_and_grad(config, p, batch, quant)
        p, m, v, gc = _adamw(opt, p, g, m, v, step)
        return p, m, v, loss, _norms(gc)
    return jax.jit(step_fn, donate_argnums=(1, 2))


def train_readings(config, opt, params, batches, quant=False):
    """Run the reference over ``batches`` from ``params`` (a weights.make
    tree, stored dtype).  Returns per-step losses, per-leaf norms of the
    first clipped gradient, and per-leaf norms of the change of the
    parameters after the last step."""
    if len(batches) >= 0.9 * opt["total_steps"]:
        raise ValueError("the reference's schedule stops before the decay")
    step_fn = train_step(config, opt, quant)
    p0 = params
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    p, losses, g1 = params, [], None
    for i, b in enumerate(batches):
        b = {k: jnp.asarray(b[k]) for k in ("tokens", "targets")}
        p, m, v, loss, gn = step_fn(p, m, v, b, jnp.float32(i))
        losses.append(float(loss))
        if g1 is None:
            g1 = np.asarray(gn, np.float64)
    change = jax.jit(lambda a, b: _norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    return {"losses": losses, "grad_norms": g1,
            "change_norms": np.asarray(change(p, p0), np.float64)}


def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree.leaves(tree)])


# ---------------------------------------------------------------------------
# serving: logits at every position of the sequences that were served
# ---------------------------------------------------------------------------

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
