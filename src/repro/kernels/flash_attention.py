"""Causal GQA flash attention as a Pallas TPU kernel.

TPU-native adaptation (DESIGN.md §7): q-block x kv-block tiles staged through
VMEM with MXU-aligned (multiple-of-128) matmul dims, online softmax carried in
VMEM scratch across the kv grid dimension (the 'arbitrary' innermost axis),
and blocks entirely above the diagonal / outside the sliding window skipped
with pl.when — the causal-skip schedule the XLA path approximates with its
'triangular' python-loop schedule.

Fused-mask fast path: a (q, kv) tile that is FULLY inside the causal
region and fully inside the sliding window needs no mask at all — only
diagonal tiles and window-edge tiles pay the iota + select.  The two
cases are split with ``pl.when`` so interior tiles run a pure
matmul/softmax-update body; for causal attention at long S this removes
the mask arithmetic from ~half of all live tiles (and from ALL tiles of
the non-causal, non-windowed case).

Block sizes: pass explicit ``q_block``/``kv_block``, or leave them
``None`` to consult the on-disk autotuner cache (``kernels/tuning.py``,
keyed by shape/dtype/backend) with a 256/256 fallback — see
docs/performance.md.

Layout: q [B*H, S, D]; k,v [B*K, S, D]; grid (B*H, nq, nk).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning

NEG_INF = -1e30

DEFAULT_BLOCKS = {"q_block": 256, "kv_block": 256}
#: candidate tile shapes for the autotuner (q_block, kv_block)
BLOCK_CANDIDATES = ((128, 128), (128, 256), (256, 256), (256, 512),
                    (512, 256), (512, 512))


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, q_block, kv_block, n_kv, causal, window):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * q_block
    k_start = ki * kv_block
    live = k_start <= q_start + q_block - 1 if causal else ki >= 0
    # tile fully below the diagonal: no causal masking needed anywhere in it
    full = k_start + kv_block - 1 <= q_start if causal else ki >= 0
    if window is not None:
        live = jnp.logical_and(live,
                               k_start + kv_block - 1 >= q_start - window + 1)
        # oldest (q, k) pair in the tile still inside the window
        full = jnp.logical_and(
            full, (q_start + q_block - 1) - k_start < window)

    def _update(s):
        """Online-softmax accumulate of one scores tile (shared by the
        masked edge path and the unmasked interior path)."""
        v = v_ref[0].astype(jnp.float32)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    def _scores():
        q = q_ref[0].astype(jnp.float32) * scale          # [qb, D]
        k = k_ref[0].astype(jnp.float32)                  # [kb, D]
        return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    masked = causal or window is not None

    @pl.when(jnp.logical_and(live, full) if masked else live)
    def _compute_full():
        # interior tile: every (q, k) pair is valid — pure matmul + update
        _update(_scores())

    if masked:
        @pl.when(jnp.logical_and(live, jnp.logical_not(full)))
        def _compute_edge():
            # diagonal / window-edge tile: one fused causal+window select
            s = _scores()
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = kpos <= qpos if causal else kpos == kpos
            if window is not None:
                mask = jnp.logical_and(mask, qpos - kpos < window)
            _update(jnp.where(mask, s, NEG_INF))

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


def _blocks_for(S, D, dtype, causal, window, q_block, kv_block):
    """Resolve block sizes: explicit args win; ``None`` consults the
    autotuner cache, falling back to the static defaults."""
    if q_block is not None and kv_block is not None:
        return q_block, kv_block
    key = tuning.make_key("flash_attention", jax.default_backend(), dtype,
                          S=S, D=D, causal=int(bool(causal)),
                          window=window or 0)
    cfg = tuning.tuned_or_default("flash_attention", key, DEFAULT_BLOCKS)
    return (q_block if q_block is not None else cfg["q_block"],
            kv_block if kv_block is not None else cfg["kv_block"])


def flash_attention(q, k, v, *, causal=True, window=None, q_block=None,
                    kv_block=None, interpret=False):
    """q: [B,H,S,D]; k,v: [B,K,S,D] (H % K == 0). Returns [B,H,S,D].

    D is zero-padded to a multiple of 128 (MXU lane width); softmax scale uses
    the true D. Scores/accumulators live in f32 VMEM scratch.
    """
    B, H, S, D = q.shape
    K = k.shape[1]
    G = H // K
    q_block, kv_block = _blocks_for(S, D, q.dtype, causal, window,
                                    q_block, kv_block)
    q_block = min(q_block, S)
    kv_block = min(kv_block, S)
    while S % q_block:
        q_block //= 2
    while S % kv_block:
        kv_block //= 2
    Dp = max(128, ((D + 127) // 128) * 128)
    if Dp != D:
        pad = [(0, 0), (0, 0), (0, 0), (0, Dp - D)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    scale = 1.0 / math.sqrt(D)
    qf = q.reshape(B * H, S, Dp)
    kf = k.reshape(B * K, S, Dp)
    vf = v.reshape(B * K, S, Dp)
    nq, nk = S // q_block, S // kv_block

    def kv_index(i, j, kk):
        # fused q row b*H + h  ->  fused kv row b*K + h // G
        return ((i // H) * K + (i % H) // G, kk, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, q_block=q_block,
                          kv_block=kv_block, n_kv=nk, causal=causal,
                          window=window),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, Dp), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, kv_block, Dp), kv_index),
            pl.BlockSpec((1, kv_block, Dp), kv_index),
        ],
        out_specs=pl.BlockSpec((1, q_block, Dp), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, Dp), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, Dp)[..., :D]


def tune(q, k, v, *, causal=True, window=None, trials=3,
         candidates=BLOCK_CANDIDATES, interpret=False):
    """Autotune (q_block, kv_block) for this call shape and persist the
    winner in the on-disk cache; returns the winning config."""
    B, H, S, D = q.shape
    key = tuning.make_key("flash_attention", jax.default_backend(), q.dtype,
                          S=S, D=D, causal=int(bool(causal)),
                          window=window or 0)

    def bench(cfg):
        fn = jax.jit(functools.partial(
            flash_attention, causal=causal, window=window,
            q_block=cfg["q_block"], kv_block=cfg["kv_block"],
            interpret=interpret))
        return lambda: fn(q, k, v)

    cands = [{"q_block": qb, "kv_block": kb} for qb, kb in candidates
             if qb <= S and kb <= S] or [DEFAULT_BLOCKS]
    return tuning.autotune("flash_attention", key, cands, bench,
                           trials=trials)
