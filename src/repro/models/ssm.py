"""Chunked gated linear recurrences: the shared math for SSD (Mamba-2: Hymba's
parallel SSM heads and the Mamba-2 mixer of hybrid layer patterns) and the
stabilized mLSTM (xLSTM).

Conventions: q/k are the "read/write" vectors (C_t/B_t for SSD), v the values,
`lg` per-head log decay gates. All recurrences are validated against naive
step-by-step references in tests (and mirrored by the Pallas kernel in
repro/kernels/mlstm_chunk.py).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import causal_conv1d, causal_conv1d_step, rms_groupnorm, rmsnorm
from repro.models.params import ParamSpec


# ---------------------------------------------------------------------------
# chunked GLA: h_t = exp(lg_t) h_{t-1} + k_t v_t^T ;  y_t = q_t . h_t
# ---------------------------------------------------------------------------

def chunked_gla(q, k, v, lg, chunk=256):
    """q,k: [B,S,H,N]; v: [B,S,H,P]; lg: [B,S,H] (log decay, <=0).
    Returns (y [B,S,H,P], final_state [B,H,N,P])."""
    B, S, H, N = q.shape
    P_ = v.shape[-1]
    c = min(chunk, S)
    while S % c:
        c //= 2
    nc = S // c
    qf = q.astype(jnp.float32).reshape(B, nc, c, H, N)
    kf = k.astype(jnp.float32).reshape(B, nc, c, H, N)
    vf = v.astype(jnp.float32).reshape(B, nc, c, H, P_)
    lgf = lg.astype(jnp.float32).reshape(B, nc, c, H)
    cum = jnp.cumsum(lgf, axis=2)                       # inclusive within-chunk
    total = cum[:, :, -1]                               # [B,nc,H]

    # intra-chunk: w_ij = exp(cum_i - cum_j) for j <= i (decay strictly after j)
    def one_chunk(qc, kc, vc, cumc, totc):
        # qc,kc: [B,c,H,N]; vc: [B,c,H,P]; cumc: [B,c,H]
        s = jnp.einsum("bihn,bjhn->bhij", qc, kc)
        dec = cumc.transpose(0, 2, 1)[:, :, :, None] - cumc.transpose(0, 2, 1)[:, :, None, :]
        mask = jnp.tril(jnp.ones((qc.shape[1], qc.shape[1]), bool))
        # masked before the exp: above the diagonal dec grows with the
        # chunk's total decay, and exp(dec) = inf would make the gradient nan
        w = jnp.exp(jnp.where(mask[None, None], dec, -jnp.inf))
        y_intra = jnp.einsum("bhij,bjhp->bihp", s * w, vc)
        # contributions for the carried state
        kdec = jnp.exp(totc[:, None, :] - cumc)          # [B,c,H]
        k_scaled = kc * kdec[..., None]
        dstate = jnp.einsum("bjhn,bjhp->bhnp", k_scaled, vc)
        return y_intra, dstate

    qs = jnp.moveaxis(qf, 1, 0)
    ks = jnp.moveaxis(kf, 1, 0)
    vs = jnp.moveaxis(vf, 1, 0)
    cums = jnp.moveaxis(cum, 1, 0)
    tots = jnp.moveaxis(total, 1, 0)

    def body(state, xs):
        qc, kc, vc, cumc, totc = xs                      # totc: [B,H]
        y_intra, dstate = one_chunk(qc, kc, vc, cumc, totc)
        qdec = jnp.exp(cumc)                             # decay from chunk start
        y_inter = jnp.einsum("bihn,bhnp->bihp", qc * qdec[..., None], state)
        new_state = state * jnp.exp(totc)[..., None, None] + dstate
        return new_state, y_intra + y_inter

    state0 = jnp.zeros((B, H, N, P_), jnp.float32)
    final, ys = jax.lax.scan(body, state0, (qs, ks, vs, cums, tots))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, H, P_)
    return y.astype(q.dtype), final


def gla_step(q, k, v, lg, state):
    """Single-token GLA update. q,k: [B,H,N]; v: [B,H,P]; lg: [B,H]; state [B,H,N,P]."""
    sf = state * jnp.exp(lg.astype(jnp.float32))[..., None, None]
    sf = sf + jnp.einsum("bhn,bhp->bhnp", k.astype(jnp.float32), v.astype(jnp.float32))
    y = jnp.einsum("bhn,bhnp->bhp", q.astype(jnp.float32), sf)
    return y.astype(v.dtype), sf


# ---------------------------------------------------------------------------
# stabilized chunked mLSTM (exp input gates + normalizer + max-state)
# ---------------------------------------------------------------------------

def chunked_mlstm(q, k, v, ig, fg, chunk=256):
    """q,k: [B,S,H,N]; v: [B,S,H,P]; ig/fg: [B,S,H] raw gate pre-activations.
    fg passes through log-sigmoid; ig stays in log space (exp input gate).
    Returns (h [B,S,H,P], state (C [B,H,N,P], n [B,H,N], m [B,H]))."""
    B, S, H, N = q.shape
    P_ = v.shape[-1]
    c = min(chunk, S)
    while S % c:
        c //= 2
    nc = S // c
    scale = 1.0 / math.sqrt(N)
    qf = (q.astype(jnp.float32) * scale).reshape(B, nc, c, H, N)
    kf = k.astype(jnp.float32).reshape(B, nc, c, H, N)
    vf = v.astype(jnp.float32).reshape(B, nc, c, H, P_)
    igf = ig.astype(jnp.float32).reshape(B, nc, c, H)
    lf = jax.nn.log_sigmoid(fg.astype(jnp.float32)).reshape(B, nc, c, H)
    cum = jnp.cumsum(lf, axis=2)
    total = cum[:, :, -1]

    def body(carry, xs):
        C, n, m = carry
        qc, kc, vc, igc, cumc, totc = xs                 # totc: [B,H]
        # [B,H,c] layouts
        cumh = cumc.transpose(0, 2, 1)
        igh = igc.transpose(0, 2, 1)
        toth = totc
        # intra log-weights a_ij = cum_i - cum_j + ig_j (j <= i)
        a = cumh[:, :, :, None] - cumh[:, :, None, :] + igh[:, :, None, :]
        mask = jnp.tril(jnp.ones((c, c), bool))
        a = jnp.where(mask[None, None], a, -jnp.inf)
        # per-row stabilizer: max over intra weights and the inter path
        b_inter = cumh + m[..., None]                    # [B,H,c]
        m_row = jnp.maximum(a.max(-1), b_inter)
        m_row = jnp.maximum(m_row, -1e30)
        w = jnp.exp(a - m_row[..., None])                # [B,H,c,c]
        inter_w = jnp.exp(b_inter - m_row)               # [B,H,c]
        s = jnp.einsum("bihn,bjhn->bhij", qc, kc)
        qh = qc.transpose(0, 2, 1, 3)                    # [B,H,c,N]
        num = jnp.einsum("bhij,bjhp->bhip", w * s, vc) \
            + inter_w[..., None] * jnp.einsum("bhin,bhnp->bhip", qh, C)
        den = jnp.einsum("bhij,bhij->bhi", w, s) \
            + inter_w * jnp.einsum("bhin,bhn->bhi", qh, n)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_row))[..., None]
        # state update with its own stabilizer
        kdec = toth[..., None] - cumh + igh              # [B,H,c] log weight per key
        m_new = jnp.maximum(toth + m, kdec.max(-1))
        kw = jnp.exp(kdec - m_new[..., None])
        carry_scale = jnp.exp(toth + m - m_new)
        kcs = kc.transpose(0, 2, 1, 3) * kw[..., None]   # [B,H,c,N]
        C_new = carry_scale[..., None, None] * C + jnp.einsum("bhjn,bjhp->bhnp", kcs, vc)
        n_new = carry_scale[..., None] * n + kcs.sum(2)
        return (C_new, n_new, m_new), h.transpose(0, 2, 1, 3)  # -> [B,c,H,P]

    C0 = jnp.zeros((B, H, N, P_), jnp.float32)
    n0 = jnp.zeros((B, H, N), jnp.float32)
    m0 = jnp.full((B, H), -1e30, jnp.float32)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (qf, kf, vf, igf, cum, total))
    (C, n, m), hs = jax.lax.scan(body, (C0, n0, m0), xs)
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, H, P_)
    return h.astype(v.dtype), (C, n, m)


def mlstm_step(q, k, v, ig, fg, state):
    """Single-token stabilized mLSTM update. q,k: [B,H,N]; v: [B,H,P];
    ig/fg: [B,H]; state = (C,n,m)."""
    C, n, m = state
    N = q.shape[-1]
    qf = q.astype(jnp.float32) / math.sqrt(N)
    lf = jax.nn.log_sigmoid(fg.astype(jnp.float32))
    igf = ig.astype(jnp.float32)
    m_new = jnp.maximum(lf + m, igf)
    fscale = jnp.exp(lf + m - m_new)
    iscale = jnp.exp(igf - m_new)
    kf = k.astype(jnp.float32) * iscale[..., None]
    C_new = fscale[..., None, None] * C + jnp.einsum("bhn,bhp->bhnp", kf, v.astype(jnp.float32))
    n_new = fscale[..., None] * n + kf
    num = jnp.einsum("bhn,bhnp->bhp", qf, C_new)
    den = jnp.einsum("bhn,bhn->bh", qf, n_new)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return h.astype(v.dtype), (C_new, n_new, m_new)


# ---------------------------------------------------------------------------
# SSD mixer block (Hymba's SSM heads; Mamba-2 scalar-decay form)
# ---------------------------------------------------------------------------

def ssd_specs(cfg):
    s = cfg.ssm
    d = cfg.d_model
    dss = s.n_ssm_heads * s.head_dim
    return {
        "w_in": ParamSpec((d, 2 * dss + 2 * s.d_state), ("embed", "inner")),
        "conv": ParamSpec((s.d_conv, dss + 2 * s.d_state), ("conv", "inner"), init="normal", scale=0.5),
        "w_dt": ParamSpec((d, s.n_ssm_heads), ("embed", None)),
        "dt_bias": ParamSpec((s.n_ssm_heads,), (None,), init="zeros"),
        "a_log": ParamSpec((s.n_ssm_heads,), (None,), init="zeros"),
        "d_skip": ParamSpec((s.n_ssm_heads,), (None,), init="ones"),
        "norm": ParamSpec((dss,), ("inner",), init="ones"),
        "wo": ParamSpec((dss, d), ("inner", "embed")),
    }


def ssd_apply(ctx, cfg, p, x, *, mode, cache=None):
    """x: [B,S,d] or [B,d]. cache: {'state': [B,H,N,P], 'conv': [B,W-1,C]}."""
    s = cfg.ssm
    Hs, Pd, N = s.n_ssm_heads, s.head_dim, s.d_state
    dss = Hs * Pd

    if mode in ("train", "prefill"):
        B, S, _ = x.shape
        proj = x @ p["w_in"]
        pre_conv, z = proj[..., : dss + 2 * N], proj[..., dss + 2 * N:]
        u_bc = jax.nn.silu(causal_conv1d(pre_conv, p["conv"]))
        u, Bt, Ct = u_bc[..., :dss], u_bc[..., dss:dss + N], u_bc[..., dss + N:]
        dt = jax.nn.softplus((x @ p["w_dt"]) + p["dt_bias"])     # [B,S,H]
        lg = -jnp.exp(p["a_log"])[None, None] * dt               # [B,S,H] <= 0
        uh = u.reshape(B, S, Hs, Pd)
        v = uh * dt[..., None]
        q = jnp.broadcast_to(Ct[:, :, None], (B, S, Hs, N))
        k = jnp.broadcast_to(Bt[:, :, None], (B, S, Hs, N))
        y, state = chunked_gla(q, k, v, lg, chunk=s.chunk)
        y = y + uh * p["d_skip"][None, None, :, None]
        y = rms_groupnorm(y.reshape(B, S, dss), p["norm"], Hs)
        y = y * jax.nn.silu(z)
        out = y @ p["wo"]
        new_cache = None
        if mode == "prefill":
            W = s.d_conv
            new_cache = {"state": state, "conv": pre_conv[:, S - (W - 1):]}
        return out, new_cache

    # decode
    B, _ = x.shape
    proj = x @ p["w_in"]
    pre_conv, z = proj[..., : dss + 2 * N], proj[..., dss + 2 * N:]
    u_bc, conv_state = causal_conv1d_step(pre_conv, cache["conv"], p["conv"])
    u_bc = jax.nn.silu(u_bc)
    u, Bt, Ct = u_bc[..., :dss], u_bc[..., dss:dss + N], u_bc[..., dss + N:]
    dt = jax.nn.softplus((x @ p["w_dt"]) + p["dt_bias"])          # [B,H]
    lg = -jnp.exp(p["a_log"])[None] * dt
    uh = u.reshape(B, Hs, Pd)
    v = uh * dt[..., None]
    q = jnp.broadcast_to(Ct[:, None], (B, Hs, N))
    k = jnp.broadcast_to(Bt[:, None], (B, Hs, N))
    y, state = gla_step(q, k, v, lg, cache["state"])
    y = y + uh * p["d_skip"][None, :, None]
    y = rms_groupnorm(y.reshape(B, dss), p["norm"], Hs)
    y = y * jax.nn.silu(z)
    return y @ p["wo"], {"state": state, "conv": conv_state}


# ---------------------------------------------------------------------------
# Mamba-2 mixer (the "mamba" layers of a layer pattern, as published)
# ---------------------------------------------------------------------------

def mamba2_specs(cfg):
    """In-projections to z, x, B/C (one pair per group) and dt; causal conv
    over x and over B/C, with a bias where ``ssm.conv_bias``; per-head
    dt bias, A (as log) and D; the gated norm's weight; the out-projection.
    x, z and the norm follow the heads ('inner'); B/C are every head's."""
    s, d = cfg.ssm, cfg.d_model
    H, W = s.n_ssm_heads, s.d_conv
    di, gn = H * s.head_dim, 2 * s.n_groups * s.d_state
    sp = {
        "w_z": ParamSpec((d, di), ("embed", "inner")),
        "w_x": ParamSpec((d, di), ("embed", "inner")),
        "w_bc": ParamSpec((d, gn), ("embed", None)),
        "w_dt": ParamSpec((d, H), ("embed", "ssm_heads")),
        "conv_x": ParamSpec((W, di), ("conv", "inner"), scale=0.5),
        "conv_bc": ParamSpec((W, gn), ("conv", None), scale=0.5),
        "dt_bias": ParamSpec((H,), ("ssm_heads",), scale=1.0),
        "a_log": ParamSpec((H,), ("ssm_heads",), scale=1.0),
        "d_skip": ParamSpec((H,), ("ssm_heads",), init="ones"),
        "norm": ParamSpec((di,), ("inner",), init="ones"),
        "wo": ParamSpec((di, d), ("inner", "embed")),
    }
    if s.conv_bias:
        sp["conv_x_bias"] = ParamSpec((di,), ("inner",), scale=0.1)
        sp["conv_bc_bias"] = ParamSpec((gn,), (None,), scale=0.1)
    return sp


def _conv_act(p, name, x, state=None):
    """SiLU of the causal conv (plus its bias) over the sequence, or of
    one decode step given the last W-1 inputs, in float32."""
    f32 = jnp.float32
    w = p[name].astype(f32)
    if state is None:
        y, new_state = causal_conv1d(x.astype(f32), w), None
    else:
        y, new_state = causal_conv1d_step(x.astype(f32), state, w)
    if name + "_bias" in p:
        y = y + p[name + "_bias"].astype(f32)
    return jax.nn.silu(y), new_state


@jax.named_scope("mamba")
def mamba2_apply(ctx, cfg, p, x, *, mode, cache=None):
    """x: [B,S,d] (train/prefill) or [B,d] (decode).  The Mamba-2 mixer:
    dt = softplus(x w_dt + dt_bias), A = -exp(a_log); per head
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T and y_t = C_t h_t + D x_t
    (B, C of the head's group), by the chunked form; then the gate,
    y * silu(z), and an RMS norm over each group's width.
    cache: {'state': [B,H,N,P], 'conv_x': [B,W-1,di], 'conv_bc': [B,W-1,2GN]}."""
    s = cfg.ssm
    H, P, G, N = s.n_ssm_heads, s.head_dim, s.n_groups, s.d_state
    f32 = jnp.float32
    decode = mode == "decode"
    lead = x.shape[:-1]
    z = x @ p["w_z"]
    xs, bc = x @ p["w_x"], x @ p["w_bc"]
    dt = jax.nn.softplus((x @ p["w_dt"]).astype(f32) + p["dt_bias"].astype(f32))
    new_cache = None
    if decode:
        xs_act, cx = _conv_act(p, "conv_x", xs, cache["conv_x"])
        bc_act, cbc = _conv_act(p, "conv_bc", bc, cache["conv_bc"])
    else:
        xs_act, _ = _conv_act(p, "conv_x", xs)
        bc_act, _ = _conv_act(p, "conv_bc", bc)
    xh = xs_act.reshape(*lead, H, P)
    with jax.named_scope("ssd"):
        a = -jnp.exp(p["a_log"].astype(f32))
        bt, ct = (jnp.repeat(t.reshape(*lead, G, N), H // G, axis=-2)
                  for t in (bc_act[..., :G * N], bc_act[..., G * N:]))
        v, lg = xh * dt[..., None], dt * a
        if decode:
            y, state = gla_step(ct, bt, v, lg, cache["state"])
        else:
            y, state = chunked_gla(ct, bt, v, lg, chunk=s.chunk)
    y = y + xh * p["d_skip"].astype(f32)[:, None]
    y = y.reshape(*lead, H * P) * jax.nn.silu(z.astype(f32))
    y = rms_groupnorm(y, p["norm"], G, cfg.norm_eps).astype(x.dtype)
    if decode:
        new_cache = {"state": state, "conv_x": cx, "conv_bc": cbc}
    elif mode == "prefill":
        W = s.d_conv
        new_cache = {"state": state, "conv_x": xs[:, -(W - 1):],
                     "conv_bc": bc[:, -(W - 1):]}
    return y @ p["wo"], new_cache
