"""Core layers: norms, RoPE, memory-efficient chunked attention (the XLA reference
path for the Pallas flash kernel), split-KV decode attention (flash-decoding under
shard_map), SwiGLU MLP, GShard-style MoE with capacity dispatch, MLA.

All functions are pure; params are dict trees matching the *_specs builders.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.params import ParamSpec

NEG_INF = -1e30


def cdtype(cfg):
    return jnp.dtype(cfg.compute_dtype)


# ---------------------------------------------------------------------------
# norms / rope / conv
# ---------------------------------------------------------------------------

@jax.named_scope("norm")
def rmsnorm(x, w, eps=1e-5):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rms_groupnorm(x, w, groups, eps=1e-5):
    """Per-head RMS norm over the trailing dim split into `groups` heads."""
    *lead, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, groups, d // groups)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y.reshape(*lead, d) * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
    """x: [..., S, H, D] (or [..., H, D] with scalar/vector positions)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast over the heads axis which sits between positions and d
    cos = jnp.expand_dims(cos, axis=-2)
    sin = jnp.expand_dims(sin, axis=-2)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


def causal_conv1d(x, w):
    """Depthwise causal conv via shifted adds. x: [B,S,C], w: [W,C]."""
    W = w.shape[0]
    out = x * w[W - 1]
    for i in range(W - 1):
        shift = W - 1 - i
        shifted = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
        out = out + shifted * w[i]
    return out


def causal_conv1d_step(x, state, w):
    """Single decode step. x: [B,C], state: [B,W-1,C] (oldest first)."""
    W = w.shape[0]
    out = x * w[W - 1] + jnp.einsum("bwc,wc->bc", state, w[: W - 1])
    new_state = jnp.concatenate([state[:, 1:], x[:, None]], axis=1)
    return out, new_state


# ---------------------------------------------------------------------------
# chunked causal attention (train / prefill)
# ---------------------------------------------------------------------------

def _chunk_bounds(i, qc, kc, nk, schedule, window):
    if schedule == "triangular":
        j_hi = -(-((i + 1) * qc) // kc)  # ceil
        j_lo = 0 if window is None else max(0, (i * qc - window) // kc)
        return j_lo, min(j_hi, nk)
    return 0, nk


def chunked_attention(ctx, q, k, v, *, window=None, schedule="masked",
                      q_chunk=1024, kv_chunk=2048, pos_offset=0, scale=None):
    """Memory-efficient causal attention with online softmax.

    q: [B,S,H,D]; k, v: [B,S,H,D] (caller repeats GQA kv heads to H).
    `schedule='masked'` scans every KV chunk with a mask (paper-faithful baseline);
    `'triangular'` statically skips chunks above the diagonal / outside the window.
    Scores are scaled by `scale`, by default 1/sqrt(D).
    """
    B, S, H, D = q.shape
    dt = q.dtype
    qc = min(q_chunk, S)
    while S % qc:
        qc //= 2
    kc = min(kv_chunk, S)
    while S % kc:
        kc //= 2
    nq, nk = S // qc, S // kc
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    q = ctx.act(q, "act_batch", None, "act_heads", None)
    k = ctx.act(k, "act_batch", None, "act_heads", None)
    v = ctx.act(v, "act_batch", None, "act_heads", None)

    qs = q.reshape(B, nq, qc, H, D).astype(jnp.float32) * scale
    ks = jnp.moveaxis(k.reshape(B, nk, kc, H, D), 1, 0)  # [nk,B,kc,H,D]
    vs = jnp.moveaxis(v.reshape(B, nk, kc, H, D), 1, 0)

    outs = []
    for i in range(nq):
        j_lo, j_hi = _chunk_bounds(i, qc, kc, nk, schedule, window)
        kslice = jax.lax.slice_in_dim(ks, j_lo, j_hi, axis=0)
        vslice = jax.lax.slice_in_dim(vs, j_lo, j_hi, axis=0)
        qi = jnp.moveaxis(qs[:, i], 1, 2)  # [B,H,qc,D]
        qpos = pos_offset + i * qc + jnp.arange(qc)

        def body(carry, x, qi=qi, qpos=qpos):
            m, l, acc = carry
            kj, vj, jidx = x
            kj = jnp.moveaxis(kj, 1, 2).astype(jnp.float32)   # [B,H,kc,D]
            vj = jnp.moveaxis(vj, 1, 2).astype(jnp.float32)
            s = jnp.einsum("bhqd,bhtd->bhqt", qi, kj)
            kpos = jidx * kc + jnp.arange(kc)
            mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum("bhqt,bhtd->bhqd", p, vj)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((B, H, qc), NEG_INF, jnp.float32),
                jnp.zeros((B, H, qc), jnp.float32),
                jnp.zeros((B, H, qc, D), jnp.float32))
        xs = (kslice, vslice, jnp.arange(j_lo, j_hi))
        (m, l, acc), _ = jax.lax.scan(body, init, xs)
        out_i = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(jnp.moveaxis(out_i, 1, 2))  # [B,qc,H,D]
    out = jnp.concatenate(outs, axis=1).astype(dt)
    return ctx.act(out, "act_batch", None, "act_heads", None)


# ---------------------------------------------------------------------------
# decode attention: split-KV flash-decoding, manual SPMD over the cache seq dim
# ---------------------------------------------------------------------------

def _attn_partials(q, k, v, kpos, total_len, window, scale=None):
    """q: [B,K,G,Dk]; k: [B,Sl,K,Dk]; v: [B,Sl,K,Dv]; kpos: [Sl] global positions.
    Returns unnormalized (m, l, o) partials for a cache shard; scores are
    scaled by `scale`, by default 1/sqrt(Dk)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    valid = kpos < total_len
    if window is not None:
        valid &= kpos >= jnp.maximum(total_len - window, 0)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(jnp.isfinite(m)[..., None], p, 0.0)
    l = p.sum(-1)
    o = jnp.einsum("bkgs,bskv->bkgv", p, v.astype(jnp.float32))
    return m, l, o


def _local_row_update(cache, row, pos, offset):
    """Write `row` [B,1,F] into the local cache shard iff pos lands in it."""
    lpos = pos - offset
    Sl = cache.shape[1]
    in_range = (lpos >= 0) & (lpos < Sl)
    idx = jnp.clip(lpos, 0, Sl - 1)
    old = jax.lax.dynamic_slice(cache, (0, idx, 0), (cache.shape[0], 1, cache.shape[2]))
    new = jnp.where(in_range, row.astype(cache.dtype), old)
    return jax.lax.dynamic_update_slice(cache, new, (0, idx, 0))


def decode_attention(ctx, q, k_cache, v_cache, k_new, v_new, pos, *, n_kv_heads,
                     window=None, v_dim=None, scale=None):
    """One-token attention over a (possibly huge) cache, with the cache-row write
    performed inside the shard_map (so a sequence-sharded cache is never gathered).

    q: [B, H*Dk]; k_cache: [B, S, K*Dk]; v_cache: [B, S, K*Dv];
    k_new/v_new: [B, K*D] rows for position `pos` (pass None to skip the write).
    Each cache shard computes flash-decoding partials, combined with a
    renormalizing psum over the cache-sequence mesh axes.
    Returns (out [B, H*Dv], k_cache', v_cache').
    """
    B, S, KDk = k_cache.shape
    K = n_kv_heads
    Dk = KDk // K
    Dv = v_dim if v_dim is not None else v_cache.shape[-1] // K
    H = q.shape[-1] // Dk
    G = H // K
    q4 = q.reshape(B, K, G, Dk)
    shared_kv = k_new is v_new  # MLA: one fused latent cache

    seq_axes = ctx.kv_seq_axes()
    batch_spec = ctx.batch_axes()

    def local(qx, kx, vx, kn, vn, tpos):
        if seq_axes:
            flat = jnp.int32(0)
            for ax in seq_axes:
                flat = flat * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
            offset = flat * kx.shape[1]
        else:
            offset = 0
        kx = _local_row_update(kx, kn[:, None], tpos, offset)
        vx = kx if shared_kv else _local_row_update(vx, vn[:, None], tpos, offset)
        k4 = kx.reshape(kx.shape[0], kx.shape[1], K, Dk).astype(jnp.float32)
        v4 = vx[..., : K * Dv].reshape(vx.shape[0], vx.shape[1], K, Dv).astype(jnp.float32)
        kpos = offset + jnp.arange(kx.shape[1])
        m, l, o = _attn_partials(qx, k4, v4, kpos, tpos + 1, window, scale)
        if seq_axes:
            m_g = jax.lax.pmax(m, seq_axes)
            corr = jnp.exp(m - m_g)
            l = jax.lax.psum(l * corr, seq_axes)
            o = jax.lax.psum(o * corr[..., None], seq_axes)
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return out, kx, vx

    if ctx.mesh is None or not seq_axes:
        out, kc, vc = local(q4, k_cache, v_cache, k_new, v_new, pos)
    else:
        seq_spec = seq_axes if len(seq_axes) > 1 else seq_axes[0]
        cache_spec = P(batch_spec, seq_spec, None)
        fn = jax.shard_map(
            local, mesh=ctx.mesh,
            in_specs=(P(batch_spec, None, None, None), cache_spec, cache_spec,
                      P(batch_spec, None), P(batch_spec, None), P()),
            out_specs=(P(batch_spec, None, None, None), cache_spec, cache_spec),
            check_vma=False)
        out, kc, vc = fn(q4, k_cache, v_cache, k_new, v_new, pos)
    return out.reshape(B, H * Dv), kc, vc


def ring_slot_positions(pos, W):
    """Global positions held by each ring-buffer slot after writing token `pos`."""
    slots = jnp.arange(W)
    return pos - jnp.mod(pos - slots, W)


def window_decode_attention(q, k_cache, v_cache, pos, *, n_kv_heads, window,
                            scale=None):
    """Decode attention over a ring-buffer window cache [B, W, K*D]."""
    B, W, KD = k_cache.shape
    K = n_kv_heads
    D = KD // K
    H = q.shape[-1] // D
    G = H // K
    q4 = q.reshape(B, K, G, D)
    kpos = ring_slot_positions(pos, W)
    k4 = k_cache.reshape(B, W, K, D).astype(jnp.float32)
    v4 = v_cache.reshape(B, W, K, D).astype(jnp.float32)
    valid = (kpos >= 0) & (kpos >= pos + 1 - window)
    m, l, o = _attn_partials(q4, k4, v4, jnp.where(valid, kpos, pos + 1), pos + 1,
                             None, scale)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H * D).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (with optional sliding window) — specs + apply
# ---------------------------------------------------------------------------

def attn_specs(cfg):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, K = cfg.n_heads, cfg.n_kv_heads
    sp = {
        "wq": ParamSpec((d, H * hd), ("embed", "heads")),
        "wk": ParamSpec((d, K * hd), ("embed", "kv")),
        "wv": ParamSpec((d, K * hd), ("embed", "kv")),
        "wo": ParamSpec((H * hd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        sp["bq"] = ParamSpec((H * hd,), ("heads",), init="zeros")
        sp["bk"] = ParamSpec((K * hd,), ("kv",), init="zeros")
        sp["bv"] = ParamSpec((K * hd,), ("kv",), init="zeros")
    return sp


@jax.named_scope("attention")
def attn_apply(ctx, cfg, p, x, *, mode, window=None, cache=None, pos=None,
               use_ring=False):
    """x: [B,S,d] (train/prefill) or [B,d] (decode).  Rotary positions
    unless ``cfg.position_embedding`` is "nope"; scores scaled by
    ``cfg.attention_multiplier`` (None: 1/sqrt(head_dim)).
    Returns (out, new_cache). Cache layout:
      full:  {'k': [B,S_max,K*hd], 'v': ...}   (written at absolute positions)
      ring:  {'k': [B,W,K*hd], 'v': ...}       (sliding-window ring buffer)
    """
    hd = cfg.resolved_head_dim
    H, K = cfg.n_heads, cfg.n_kv_heads
    G = H // K
    theta = cfg.rope_theta
    nope = cfg.position_embedding == "nope"
    scale = cfg.attention_multiplier

    if mode in ("train", "prefill"):
        B, S, _ = x.shape
        positions = jnp.arange(S)
        q = jnp.einsum("bsd,df->bsf", x, p["wq"])
        k = jnp.einsum("bsd,df->bsf", x, p["wk"])
        v = jnp.einsum("bsd,df->bsf", x, p["wv"])
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        place = (lambda t: t) if nope else (lambda t: rope(t, positions, theta))
        q = place(q.reshape(B, S, H, hd))
        k = place(k.reshape(B, S, K, hd))
        v = v.reshape(B, S, K, hd)
        kr = jnp.repeat(k, G, axis=2)
        vr = jnp.repeat(v, G, axis=2)
        o = chunked_attention(ctx, q, kr, vr, window=window,
                              schedule=cfg.attn_schedule,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                              scale=scale)
        out = jnp.einsum("bsf,fd->bsd", o.reshape(B, S, H * hd), p["wo"])
        new_cache = None
        if mode == "prefill":
            cdt = jnp.dtype(cfg.cache_dtype)
            kf = k.reshape(B, S, K * hd)
            vf = v.reshape(B, S, K * hd)
            if use_ring:
                W = window
                # keep the last `window` tokens in ring order: slot = pos % W
                tail_k = kf[:, -W:]
                tail_v = vf[:, -W:]
                roll = (S % W)
                tail_k = jnp.roll(tail_k, roll, axis=1)
                tail_v = jnp.roll(tail_v, roll, axis=1)
                new_cache = {"k": tail_k.astype(cdt), "v": tail_v.astype(cdt)}
            else:
                new_cache = {"k": ctx.act(kf.astype(cdt), "act_batch", "act_kv_seq", None),
                             "v": ctx.act(vf.astype(cdt), "act_batch", "act_kv_seq", None)}
        return out, new_cache

    # --- decode: x [B,d], pos scalar int32 = index of the incoming token ---
    B, _ = x.shape
    q = jnp.einsum("bd,df->bf", x, p["wq"])
    k = jnp.einsum("bd,df->bf", x, p["wk"])
    v = jnp.einsum("bd,df->bf", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if not nope:
        posv = jnp.full((B,), pos)
        q = rope(q.reshape(B, H, hd), posv, theta).reshape(B, H * hd)
        k = rope(k.reshape(B, K, hd), posv, theta).reshape(B, K * hd)
    cdt = cache["k"].dtype
    if use_ring:
        W = cache["k"].shape[1]
        slot = jnp.mod(pos, W)
        kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cdt)[:, None], (0, slot, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cdt)[:, None], (0, slot, 0))
        o = window_decode_attention(q, kc, vc, pos, n_kv_heads=K, window=window,
                                    scale=scale)
    else:
        o, kc, vc = decode_attention(ctx, q, cache["k"], cache["v"], k, v, pos,
                                     n_kv_heads=K, window=window, scale=scale)
    out = jnp.einsum("bf,fd->bd", o, p["wo"])
    return out, {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_specs(cfg):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    return {
        "wq_a": ParamSpec((d, m.q_lora_rank), ("embed", "lora")),
        "q_ln": ParamSpec((m.q_lora_rank,), ("lora",), init="ones"),
        "wq_b": ParamSpec((m.q_lora_rank, H * (m.qk_nope_dim + m.qk_rope_dim)),
                          ("lora", "heads")),
        "wkv_a": ParamSpec((d, m.kv_lora_rank + m.qk_rope_dim), ("embed", "lora")),
        "kv_ln": ParamSpec((m.kv_lora_rank,), ("lora",), init="ones"),
        "wkv_b": ParamSpec((m.kv_lora_rank, H * (m.qk_nope_dim + m.v_head_dim)),
                           ("lora", "heads")),
        "wo": ParamSpec((H * m.v_head_dim, d), ("heads", "embed")),
    }


@jax.named_scope("attention")
def mla_apply(ctx, cfg, p, x, *, mode, cache=None, pos=None):
    m = cfg.mla
    H = cfg.n_heads
    nope, rd, vd, r = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim, m.kv_lora_rank
    theta = cfg.rope_theta
    wkv_b = p["wkv_b"].reshape(r, H, nope + vd)
    wk_b, wv_b = wkv_b[..., :nope], wkv_b[..., nope:]

    if mode in ("train", "prefill"):
        B, S, _ = x.shape
        positions = jnp.arange(S)
        cq = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_ln"])
        q = jnp.einsum("bsr,rf->bsf", cq, p["wq_b"]).reshape(B, S, H, nope + rd)
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], positions, theta)
        ckv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
        c = rmsnorm(ckv[..., :r], p["kv_ln"])
        k_rope = rope(ckv[..., None, r:], positions, theta)  # [B,S,1,rd]
        k_nope = jnp.einsum("bsr,rhn->bshn", c, wk_b)
        v = jnp.einsum("bsr,rhv->bshv", c, wv_b)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rd))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        # pad v to match qk head_dim for the shared attention core, slice after
        vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, nope + rd - vd)))
        o = chunked_attention(ctx, q, k, vpad, schedule=cfg.attn_schedule,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        o = o.reshape(B, S, H, nope + rd)[..., :vd]
        out = jnp.einsum("bsf,fd->bsd", o.reshape(B, S, H * vd), p["wo"])
        new_cache = None
        if mode == "prefill":
            cdt = jnp.dtype(cfg.cache_dtype)
            lat = jnp.concatenate([c, k_rope[:, :, 0]], axis=-1)  # [B,S,r+rd]
            new_cache = {"lat": ctx.act(lat.astype(cdt), "act_batch", "act_kv_seq", None)}
        return out, new_cache

    # --- decode (absorbed latent attention) ---
    B, _ = x.shape
    posv = jnp.full((B,), pos)
    cq = rmsnorm(jnp.einsum("bd,dr->br", x, p["wq_a"]), p["q_ln"])
    q = jnp.einsum("br,rf->bf", cq, p["wq_b"]).reshape(B, H, nope + rd)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], posv, theta)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, wk_b)
    q_eff = jnp.concatenate([q_lat, q_rope], axis=-1).reshape(B, H * (r + rd))
    ckv = jnp.einsum("bd,dr->br", x, p["wkv_a"])
    c = rmsnorm(ckv[..., :r], p["kv_ln"])
    k_rope = rope(ckv[:, None, r:], posv, theta)[:, 0]
    row = jnp.concatenate([c, k_rope], axis=-1)
    o_lat, lat, _ = decode_attention(ctx, q_eff, cache["lat"], cache["lat"],
                                     row, row, pos, n_kv_heads=1, v_dim=r)
    o_lat = o_lat.reshape(B, H, r)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wv_b).reshape(B, H * vd)
    out = jnp.einsum("bf,fd->bd", o, p["wo"])
    return out, {"lat": lat}


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def mlp_specs(cfg, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi": ParamSpec((d, f), ("embed", "mlp")),
        "wg": ParamSpec((d, f), ("embed", "mlp")),
        "wo": ParamSpec((f, d), ("mlp", "embed")),
    }


@jax.named_scope("mlp")
def mlp_apply(ctx, p, x):
    h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    h = ctx.act(h, "act_batch", None, "act_mlp") if h.ndim == 3 else h
    return h @ p["wo"]


def moe_specs(cfg):
    mo = cfg.moe
    d, f, E = cfg.d_model, mo.expert_d_ff, mo.n_experts
    sp = {
        "router": ParamSpec((d, E), ("embed", None)),
        "wi": ParamSpec((E, d, f), ("expert", "expert_in", "expert_mlp")),
        "wg": ParamSpec((E, d, f), ("expert", "expert_in", "expert_mlp")),
        "wo": ParamSpec((E, f, d), ("expert", "expert_mlp", "expert_in")),
    }
    if mo.dense_residual:
        sp["dense"] = mlp_specs(cfg)
    return sp


def _topk_dispatch(gates, k, C):
    """gates: [B,s,E] softmax probs. Returns dispatch/combine [B,s,E,C] + aux stats."""
    B, s, E = gates.shape
    g = gates
    counts = jnp.zeros((B, E), jnp.float32)
    dispatch = jnp.zeros((B, s, E, C), jnp.float32)
    combine = jnp.zeros((B, s, E, C), jnp.float32)
    selprob = jnp.zeros((B, s), jnp.float32)
    first_choice = jnp.zeros((B, s, E), jnp.float32)
    for slot in range(k):
        idx = jnp.argmax(g, axis=-1)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        if slot == 0:
            first_choice = onehot
        pos_in = jnp.cumsum(onehot, axis=1) - onehot + counts[:, None, :]
        pos = jnp.sum(pos_in * onehot, axis=-1).astype(jnp.int32)  # [B,s]
        keep = (pos < C).astype(jnp.float32)
        w = jnp.sum(gates * onehot, axis=-1)
        slot_d = onehot[..., None] * jax.nn.one_hot(pos, C, dtype=jnp.float32)[..., None, :]
        slot_d = slot_d * keep[..., None, None]
        dispatch = dispatch + slot_d
        combine = combine + slot_d * w[..., None, None]
        selprob = selprob + w * keep
        counts = counts + onehot.sum(axis=1)
        g = g * (1.0 - onehot)
    combine = combine / jnp.maximum(selprob, 1e-9)[..., None, None]
    return dispatch, combine, first_choice


@jax.named_scope("moe")
def moe_apply(ctx, cfg, p, x, *, mode):
    """GShard-style capacity dispatch over sequence chunks. x: [B,S,d] or [B,d]."""
    mo = cfg.moe
    E, k = mo.n_experts, mo.top_k
    dt = x.dtype

    if mode == "decode":
        # grouped-GEMV path: gather only the selected experts' weights
        logits = (x @ p["router"]).astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(gates, k)           # [B,k]
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        wi = jnp.take(p["wi"], top_i, axis=0)            # [B,k,d,f]
        wg = jnp.take(p["wg"], top_i, axis=0)
        wo = jnp.take(p["wo"], top_i, axis=0)
        h = jax.nn.silu(jnp.einsum("bd,bkdf->bkf", x, wg)) * jnp.einsum("bd,bkdf->bkf", x, wi)
        y = jnp.einsum("bkf,bkfd->bkd", h, wo)
        out = jnp.einsum("bkd,bk->bd", y, top_w.astype(dt))
        if mo.dense_residual:
            out = out + mlp_apply(ctx, p["dense"], x)
        return out, jnp.zeros((), jnp.float32)

    B, S, d = x.shape
    gs = math.gcd(min(mo.group_size, S), S)
    nchunk = S // gs
    C = max(1, int(math.ceil(gs * k / E * mo.capacity_factor)))
    # MoE blocks may use a different batch sharding than the dense blocks
    # (ZeRO-3 batch-over-all is wrong for expert weights: the grad reduction
    # would move the full expert grads per device — see EXPERIMENTS.md §Perf)
    x = ctx.act(x, "act_moe_batch", None, None)
    xs = jnp.moveaxis(x.reshape(B, nchunk, gs, d), 1, 0)  # [nchunk,B,gs,d]

    def chunk_fn(carry, xc):
        logits = (xc @ p["router"]).astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        dispatch, combine, first = _topk_dispatch(gates, k, C)
        xe = jnp.einsum("bsec,bsd->becd", dispatch.astype(dt), xc)
        xe = ctx.act(xe, "act_moe_batch", "act_expert", None, None)
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, p["wg"]))
        h = h * jnp.einsum("becd,edf->becf", xe, p["wi"])
        ye = jnp.einsum("becf,efd->becd", h, p["wo"])
        yc = jnp.einsum("becd,bsec->bsd", ye, combine.astype(dt))
        # aux losses (Switch-style load balance + router z-loss)
        frac_tokens = first.mean(axis=1)                      # [B,E]
        mean_prob = gates.mean(axis=1)
        lb = E * jnp.mean(jnp.sum(frac_tokens * mean_prob, axis=-1))
        zl = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        aux = mo.load_balance_loss * lb + mo.router_z_loss * zl
        return carry + aux, yc

    aux, ys = jax.lax.scan(chunk_fn, jnp.zeros((), jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, d)
    if mo.dense_residual:
        y = y + mlp_apply(ctx, p["dense"], x)
    y = ctx.act(y, "act_batch", None, None)   # back to the dense-block layout
    return y, aux / nchunk
