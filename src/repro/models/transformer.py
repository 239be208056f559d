"""Decoder-stack assembly: per-family block definitions, segment planning
(scanned homogeneous runs + unscanned exceptional layers), embeddings, heads.

A ``layer_types`` pattern (hybrid models) gives each layer its mixer: each
run of "mamba" layers is one scanned segment, each "attention" layer a
segment of its own.  Granite's multipliers scale the embedding, every
sub-block's output before its residual add, and the logits.

Segments keep compile time bounded at 512-way SPMD: a 60-layer dense model is a
single `lax.scan` over stacked params with (optionally) a remat'd body.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.models.params import ParamSpec, stack_spec

VISION_DIM = 1024  # stubbed llava frontend output width


@dataclass(frozen=True)
class Segment:
    kind: str            # 'attn' | 'hymba' | 'xlstm_pair' | 'mamba'
    n: int               # number of block repetitions in this segment
    scanned: bool
    window: Optional[int]  # None = full attention


def plan_segments(cfg):
    if cfg.layer_types:
        assert len(cfg.layer_types) == cfg.n_layers, cfg.layer_types
        segs = []
        for kind in cfg.layer_types:
            if kind == "attention":
                segs.append(Segment("attn", 1, False, cfg.window))
            elif kind != "mamba":
                raise ValueError(f"unknown layer type {kind!r}")
            elif segs and segs[-1].kind == "mamba":
                segs[-1] = Segment("mamba", segs[-1].n + 1, True, None)
            else:
                segs.append(Segment("mamba", 1, True, None))
        return segs
    if cfg.block == "xlstm":
        assert cfg.n_layers % 2 == 0
        return [Segment("xlstm_pair", cfg.n_layers // 2, True, None)]
    if cfg.block == "hymba":
        gl = sorted(cfg.global_layers)
        segs, prev = [], 0
        for g in gl:
            if g > prev:
                segs.append(Segment("hymba", g - prev, True, cfg.window))
            segs.append(Segment("hymba", 1, False, None))  # global-attention layer
            prev = g + 1
        if prev < cfg.n_layers:
            segs.append(Segment("hymba", cfg.n_layers - prev, True, cfg.window))
        return segs
    return [Segment("attn", cfg.n_layers, True, cfg.window)]


# ---------------------------------------------------------------------------
# block specs / apply
# ---------------------------------------------------------------------------

def block_specs(cfg, kind):
    d = cfg.d_model
    if kind == "xlstm_pair":
        return {
            "m_norm": ParamSpec((d,), ("embed",), init="ones"),
            "mlstm": X.mlstm_specs(cfg),
            "s_norm": ParamSpec((d,), ("embed",), init="ones"),
            "slstm": X.slstm_specs(cfg),
        }
    sp = {"ln1": ParamSpec((d,), ("embed",), init="ones")}
    if kind == "mamba":
        sp["mamba"] = S.mamba2_specs(cfg)
    else:
        sp["attn"] = L.mla_specs(cfg) if cfg.mla is not None else L.attn_specs(cfg)
    if kind == "hymba":
        sp["ssd"] = S.ssd_specs(cfg)
    if cfg.moe is not None:
        sp["ln2"] = ParamSpec((d,), ("embed",), init="ones")
        sp["ffn"] = L.moe_specs(cfg)
    elif cfg.d_ff:
        sp["ln2"] = ParamSpec((d,), ("embed",), init="ones")
        sp["ffn"] = L.mlp_specs(cfg)
    return sp


def _residual(cfg, x, out):
    """x plus a sub-block's output, scaled by the residual multiplier."""
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out


def block_apply(ctx, cfg, kind, p, x, *, mode, window, cache=None, pos=None):
    """Returns (x_out, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "xlstm_pair":
        h, mc = X.mlstm_apply(ctx, cfg, p["mlstm"], L.rmsnorm(x, p["m_norm"]),
                              mode=mode, cache=None if cache is None else cache["mlstm"])
        x = x + h
        h, sc = X.slstm_apply(ctx, cfg, p["slstm"], L.rmsnorm(x, p["s_norm"]),
                              mode=mode, cache=None if cache is None else cache["slstm"])
        x = x + h
        nc = None if mc is None and sc is None else {"mlstm": mc, "slstm": sc}
        return x, nc, aux

    xn = L.rmsnorm(x, p["ln1"])
    use_ring = window is not None
    if kind == "mamba":
        a_out, a_cache = S.mamba2_apply(
            ctx, cfg, p["mamba"], xn, mode=mode,
            cache=None if cache is None else cache["mamba"])
    elif cfg.mla is not None:
        a_out, a_cache = L.mla_apply(ctx, cfg, p["attn"], xn, mode=mode,
                                     cache=None if cache is None else cache["attn"],
                                     pos=pos)
    else:
        a_out, a_cache = L.attn_apply(ctx, cfg, p["attn"], xn, mode=mode,
                                      window=window,
                                      cache=None if cache is None else cache["attn"],
                                      pos=pos, use_ring=use_ring)
    if kind == "hymba":
        s_out, s_cache = S.ssd_apply(ctx, cfg, p["ssd"], xn, mode=mode,
                                     cache=None if cache is None else cache["ssd"])
        x = _residual(cfg, x, 0.5 * (a_out + s_out))
    else:
        s_cache = None
        x = _residual(cfg, x, a_out)

    if "ffn" in p:
        xn2 = L.rmsnorm(x, p["ln2"])
        if cfg.moe is not None:
            f_out, moe_aux = L.moe_apply(ctx, cfg, p["ffn"], xn2, mode=mode)
            aux = aux + moe_aux
        else:
            f_out = L.mlp_apply(ctx, p["ffn"], xn2)
        x = _residual(cfg, x, f_out)

    nc = None
    if a_cache is not None and kind == "mamba":
        nc = {"mamba": a_cache}
    elif a_cache is not None or s_cache is not None:
        nc = {"attn": a_cache}
        if kind == "hymba":
            nc["ssd"] = s_cache
    return x, nc, aux


# ---------------------------------------------------------------------------
# full model specs
# ---------------------------------------------------------------------------

def model_specs(cfg):
    d, Vp = cfg.d_model, cfg.padded_vocab
    sp = {}
    if cfg.n_codebooks > 1:
        sp["embed"] = ParamSpec((cfg.n_codebooks, Vp, d), (None, "vocab", "embed"),
                                init="embed")
    else:
        sp["embed"] = ParamSpec((Vp, d), ("vocab", "embed"), init="embed")
    if cfg.img_tokens:
        sp["mm_proj"] = ParamSpec((VISION_DIM, d), (None, "embed"))
    sp["segments"] = []
    for seg in plan_segments(cfg):
        bs = block_specs(cfg, seg.kind)
        sp["segments"].append(stack_spec(bs, seg.n) if seg.scanned else bs)
    sp["final_norm"] = ParamSpec((d,), ("embed",), init="ones")
    if cfg.tie_embeddings:
        assert cfg.n_codebooks == 1, "tied embeddings need one codebook"
    else:
        sp["head"] = ParamSpec((d, cfg.n_codebooks * Vp), ("embed", "vocab"))
    return sp


@jax.named_scope("embed")
def embed_tokens(ctx, cfg, params, tokens, patch_embeds=None):
    emb = params["embed"]
    if cfg.n_codebooks > 1:
        # tokens: [B, K, S] -> sum of per-codebook embeddings
        parts = [jnp.take(emb[k], tokens[:, k], axis=0)
                 for k in range(cfg.n_codebooks)]
        h = sum(parts)
    else:
        h = jnp.take(emb, tokens, axis=0)
    if cfg.img_tokens and patch_embeds is not None:
        vis = jnp.einsum("bnv,vd->bnd", patch_embeds.astype(h.dtype), params["mm_proj"])
        if h.ndim == 3:
            h = jnp.concatenate([vis, h[:, cfg.img_tokens:]], axis=1)
    h = h.astype(jnp.dtype(cfg.compute_dtype))
    if cfg.embedding_multiplier != 1.0:
        h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    axes = ("act_batch",) + (None,) * (h.ndim - 1)
    return ctx.act(h, *axes)


@jax.named_scope("head")
def lm_head(ctx, cfg, params, h):
    """h: [..., d] -> logits [..., n_codebooks * padded_vocab] (f32), over
    the embedding's rows where the embeddings are tied, divided by the
    logits scaling."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h, params["embed"])
    else:
        logits = jnp.einsum("...d,dv->...v", h, params["head"])
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if h.ndim == 3:
        logits = ctx.act(logits, "act_batch", None, "act_vocab")
    else:
        logits = ctx.act(logits, "act_batch", "act_vocab")
    return logits


def _seg_body(ctx, cfg, seg, mode):
    def body(x, p, cache=None, pos=None):
        return block_apply(ctx, cfg, seg.kind, p, x, mode=mode,
                           window=seg.window, cache=cache, pos=pos)
    return body


def run_segments(ctx, cfg, params, h, *, mode, caches=None, pos=None):
    """Runs all segments. Returns (h, new_caches, aux_sum).

    caches: list (one entry per segment); scanned segments carry a stacked
    [n, ...] cache pytree consumed/produced via lax.scan xs/ys.
    """
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for si, seg in enumerate(plan_segments(cfg)):
        p = params["segments"][si]
        body = _seg_body(ctx, cfg, seg, mode)
        cache = None if caches is None else caches[si]
        if not seg.scanned:
            h, nc, aux = body(h, p, cache, pos)
            aux_total = aux_total + aux
            new_caches.append(nc)
            continue

        if mode == "train":
            def scan_fn(x, pl):
                y, _, aux = body(x, pl)
                return y, aux
            if cfg.remat:
                scan_fn = jax.checkpoint(
                    scan_fn, policy=jax.checkpoint_policies.nothing_saveable)
            h, auxs = jax.lax.scan(scan_fn, h, p)
            aux_total = aux_total + auxs.sum()
            new_caches.append(None)
        elif mode == "prefill":
            def scan_fn(x, pl):
                y, nc, aux = body(x, pl)
                return y, (nc, aux)
            h, (ncs, auxs) = jax.lax.scan(scan_fn, h, p)
            aux_total = aux_total + auxs.sum()
            new_caches.append(ncs)
        else:  # decode
            def scan_fn(x, pc):
                pl, cl = pc
                y, nc, aux = body(x, pl, cl, pos)
                return y, nc
            h, ncs = jax.lax.scan(scan_fn, h, (p, cache))
            new_caches.append(ncs)
    return h, new_caches, aux_total
