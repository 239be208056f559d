"""Step builders: train_step (fwd+bwd+optimizer), prefill_step, decode_step —
plus ShapeDtypeStruct input specs and sharding trees for jit/lower (the dry-run
and the real launcher share these).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.tracing import span
from repro.models import Model
from repro.models.transformer import VISION_DIM
from repro.models.params import ParamSpec, is_spec
from repro.optim.optimizers import global_norm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

@jax.named_scope("loss")
def lm_loss(cfg, logits, targets):
    """logits: [B,S,K*Vp] float32; targets: [B,S] or [B,K,S] int32.
    Padded-vocab logits are masked out of the logsumexp."""
    Vp, V, K = cfg.padded_vocab, cfg.vocab_size, cfg.n_codebooks
    B, S = logits.shape[0], logits.shape[1]
    lg = logits.reshape(B, S, K, Vp)
    pad_mask = (jnp.arange(Vp) >= V)[None, None, None, :]
    lg = jnp.where(pad_mask, NEG_INF, lg)
    lse = jax.nn.logsumexp(lg, axis=-1)                    # [B,S,K]
    if K > 1:
        tgt = jnp.moveaxis(targets, 1, 2)                  # [B,K,S] -> [B,S,K]
    else:
        tgt = targets[..., None]
    tgt_logit = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt_logit)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def make_train_step(model: Model, ctx, optimizer):
    cfg = model.cfg

    def train_step(params, opt_state, batch, step):
        def loss_fn(p):
            logits, aux = model.train_logits(ctx, p, batch)
            loss = lm_loss(cfg, logits, batch["targets"])
            return loss + aux, (loss, aux)

        grads, (total, (loss, aux)) = _grad_with_aux(loss_fn, params)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, opt_state, params,
                                                   step)
        metrics = {"loss": loss, "aux_loss": aux, "total_loss": total,
                   "grad_norm": global_norm(grads), "step": step + 1}
        return new_params, new_opt, metrics

    return train_step


def _grad_with_aux(loss_fn, params):
    (total, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    return grads, (total, aux)


class AllreduceHandle:
    """Late-wait half of :func:`host_allreduce_async`; ``wait()`` returns
    the folded scalar (the rank-0 copy, identical on every rank)."""

    def __init__(self, coll_handle):
        self._h = coll_handle

    @property
    def done(self) -> bool:
        return self._h.done

    def wait(self):
        return self._h.wait()[0]


def host_allreduce_async(cluster, value, op: str = "MPI_SUM", *,
                         timeout: float = 30.0,
                         step: Optional[int] = None) -> AllreduceHandle:
    """Async-start/late-wait split of :func:`host_allreduce`: the rank
    threads enter the collective NOW, the caller keeps dispatching device
    work, and ``handle.wait()`` lands when the result is needed.

    The overlap trick: pass ``value`` as a callable ``rank -> scalar``
    closing over a device array (e.g. ``lambda r: float(metrics["loss"])``
    right after an async jit dispatch) — each rank thread then blocks on
    the device transfer INSIDE the collective pool while the main thread
    (and the device) keep going, so collective latency hides behind
    backward/optimizer compute instead of adding to it.  Exactly one
    allreduce may be in flight per cluster; wait before starting the next
    collective (see docs/performance.md, "Async allreduce overlap").

    In each rank thread, ``train.loss_to_host`` spans the value callable
    and ``mpi.allreduce`` the collective once the value is on the host,
    both tagged with ``rank`` and ``step``."""
    def one(m):
        if callable(value):
            with span("train.loss_to_host", rank=m.rank, step=step):
                v = value(m.rank)
        else:
            v = value
        with span("mpi.allreduce", rank=m.rank, step=step):
            return m.allreduce(m.comm_world(), v, m.op_handles[op])
    return AllreduceHandle(cluster.run_collective_async(one, timeout=timeout))


def host_allreduce(cluster, value, op: str = "MPI_SUM", *,
                   timeout: float = 30.0):
    """World allreduce of a host scalar over the MANA plane — the training
    step's collective hot path (every live rank enters
    ``allreduce(comm_world(), value, op)`` through the interposition
    layer; capability-gated native vs derived per backend flavor).

    ``value`` may be a plain scalar (same contribution everywhere) or a
    callable ``rank -> scalar``.  Returns the rank-order fold, identical
    on every rank (the rank-0 copy)."""
    return host_allreduce_async(cluster, value, op, timeout=timeout).wait()


def make_prefill_step(model: Model, ctx):
    def prefill_step(params, batch):
        return model.prefill(ctx, params, batch)
    return prefill_step


def make_decode_step(model: Model, ctx):
    def decode_step(params, token, pos, caches):
        return model.decode_step(ctx, params, token, pos, caches)
    return decode_step


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins) + sharding trees
# ---------------------------------------------------------------------------

def batch_specs(cfg, shape, *, with_targets):
    B, S = shape.global_batch, shape.seq_len
    tok_shape = (B, cfg.n_codebooks, S) if cfg.n_codebooks > 1 else (B, S)
    d = {"tokens": jax.ShapeDtypeStruct(tok_shape, jnp.int32)}
    if with_targets:
        d["targets"] = jax.ShapeDtypeStruct(tok_shape, jnp.int32)
    if cfg.img_tokens:
        d["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.img_tokens, VISION_DIM), jnp.bfloat16)
    return d


def batch_shardings(ctx, batch_tree):
    b = ctx.batch_axes()

    def one(leaf):
        spec = [b] + [None] * (leaf.ndim - 1)
        return NamedSharding(ctx.mesh, P(*spec)) if ctx.mesh is not None else None
    return jax.tree.map(one, batch_tree)


def opt_state_specs(cfg, model_specs_tree, optimizer_name):
    """Mirror of optimizer.init as ParamSpecs (shapes + logical axes), so the
    dry-run can shard optimizer state without materializing it."""
    dt = cfg.opt_state_dtype

    def one(s: ParamSpec):
        if optimizer_name == "adafactor":
            if len(s.shape) >= 2 and s.shape[-1] >= 128 and s.shape[-2] >= 128:
                return {"vr": ParamSpec(s.shape[:-1], s.axes[:-1], init="zeros"),
                        "vc": ParamSpec(s.shape[:-2] + s.shape[-1:],
                                        s.axes[:-2] + s.axes[-1:], init="zeros")}
            return {"v": ParamSpec(s.shape, s.axes, init="zeros")}
        return s  # adamw: m and v share the param spec

    mapped = jax.tree.map(one, model_specs_tree, is_leaf=is_spec)
    if optimizer_name == "adafactor":
        return {"f": mapped}
    return {"m": mapped, "v": jax.tree.map(lambda x: x, mapped, is_leaf=is_spec)}


def specs_to_abstract(spec_tree, dtype):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
                        spec_tree, is_leaf=is_spec)


def specs_to_shardings(ctx, spec_tree):
    return jax.tree.map(lambda s: ctx.sharding(s.axes), spec_tree, is_leaf=is_spec)


def cache_shardings(ctx, cache_tree, batch_size, max_len):
    """Heuristic cache sharding. Cache leaves are [B, ...] or [n_layers, B, ...]
    (scanned segments stack a leading layers dim): the first dim equal to
    batch_size is the batch axis; the first dim equal to max_len after it is
    the sequence-sharded cache axis. Ring/window/state dims stay replicated."""
    b = ctx.batch_axes()
    seq = ctx.kv_seq_axes()
    seq_spec = (tuple(seq) if len(seq) > 1 else seq[0]) if seq else None

    def one(leaf):
        spec = [None] * leaf.ndim
        bi = None
        for i, dim in enumerate(leaf.shape):
            if dim == batch_size and i <= 1:
                bi = i
                spec[i] = b
                break
        if bi is not None:
            for i in range(bi + 1, leaf.ndim):
                if leaf.shape[i] == max_len:
                    spec[i] = seq_spec
                    break
        return NamedSharding(ctx.mesh, P(*spec)) if ctx.mesh is not None else None

    return jax.tree.map(one, cache_tree)
