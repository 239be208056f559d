"""The plain reference's shared part: the loss, three AdamW steps as the
configuration states them, and the helpers every architecture's forward
uses.  The forward itself, the block as the configuration file states it,
is the architecture's ``logits`` and ``serve_logits``
(``bench/archs/<model_type>.py``), written in ``jax.numpy`` in float32 with
every matrix product at ``Precision.HIGHEST``.  The reference imports
nothing of the program and is given only the configuration file, weights
made by ``weights.make`` from the seed, and the tokens the benchmark sent.

``quant=True`` is the control: the same reference with every matrix
product's operands rounded to float8 e4m3 (per-tensor scale, gradient
passed straight through), the precision below the bfloat16 the
configuration states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import common

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

    logits = common.arch(config).logits

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
