"""Split-KV flash-decoding Pallas kernels (one query token, huge KV cache).

Single-pass variant: the cache sequence is cut into `n_splits` slabs and the
slab axis is the sequential ('arbitrary') innermost grid dimension, with the
online-softmax carry (m, l, acc) held in VMEM scratch across slabs — so the
renormalizing combine happens *inside* the kernel and nothing but the final
[B, H, K*D] output (see Layout) ever leaves VMEM.  (The seed two-pass
version wrote per-slab unnormalized partials to HBM and renormalized in a
jnp epilogue; the single-pass form removes that 2x partials round-trip,
which matters because decode is bandwidth-bound — see
docs/performance.md.)

Paged variant: :func:`paged_decode_attention` reads K/V from a page pool
([n_pages, page_size, K, D]) through a per-sequence page table, using
``pltpu.PrefetchScalarGridSpec`` so the page indices are scalar-prefetched
and drive the BlockSpec index_map directly — the gather happens in the DMA
engine, not as an XLA gather.  This is the serving-path layout where
sequences share one physical pool and a sequence's pages are scattered.

`n_splits` is a tuned knob: pass an int, or ``None`` to consult the on-disk
autotuner cache (kernels/tuning.py) with a fallback of 8.

Layout: q [B, H, D]; k,v [B, S, K, D] -> out [B, H, D].  Inside the kernels
the cache is viewed as [.., S, K*D] (a free reshape) and the query as a
block-diagonal [H, K*D] (see :func:`_expand_q`), so every in-kernel op is a
2-D tile op that Mosaic lowers.  The zero lanes cost K times the score and
value matmul FLOPs and accumulator work, and the f32 [B, H, K*D] output is
2K times the bytes of a bf16 [B, H, D]; whether cache-bound decode hides
that is untimed, so time it before a model path dispatches here.
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

DEFAULT_SPLITS = {"n_splits": 8}
SPLIT_CANDIDATES = (1, 2, 4, 8, 16, 32)


def _expand_q(q, K):
    """[B, H, D] -> block-diagonal [B, H, K*D]: head h keeps its D values in
    the lanes of its kv head h // G and zeros elsewhere.  One 2-D matmul of
    this against a [split, K*D] cache tile then yields every head's scores,
    so the kernel needs no in-kernel [H, D] -> [K, G, D] reshape and no 3-D
    einsum (Mosaic lowers neither).  Multiplying by the 0/1 identity is
    exact in every dtype."""
    B, H, D = q.shape
    qg = q.reshape(B, K, H // K, D)
    eye = jnp.eye(K, dtype=q.dtype)
    return jnp.einsum("bkgd,kj->bkgjd", qg, eye).reshape(B, H, K * D)


def _own_heads(out, K, D):
    """[B, H, K*D] kernel output -> [B, H, D]: each head's own kv-head
    lanes (the other lanes hold its probabilities applied to other heads'
    values and are discarded)."""
    B, H, _ = out.shape
    o = out.reshape(B, K, H // K, K, D)
    return jnp.moveaxis(jnp.diagonal(o, axis1=1, axis2=3), -1, 1).reshape(
        B, H, D)


def _attend(q, kk, vv, start, length, window, scale, m_scr, l_scr, acc_scr):
    """Online-softmax update of the VMEM carry with one slab of the cache.
    q: [H, K*D] block-diagonal query; kk, vv: [split, K*D] cache rows at
    positions start..start+split-1; carry m, l: [H, 1], acc: [H, K*D]."""
    q = q.astype(jnp.float32) * scale
    s = jax.lax.dot_general(q, kk.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [H, split]
    kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < length
    if window is not None:
        valid = jnp.logical_and(valid, kpos >= length - window)
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, vv.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _init_carry(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finalize(o_ref, l_scr, acc_scr):
    o_ref[...] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


def _carry_scratch(H, width):
    return [pltpu.VMEM((H, 1), jnp.float32), pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, width), jnp.float32)]


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, split, n_splits, window):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        _init_carry(m_scr, l_scr, acc_scr)

    _attend(q_ref[...], k_ref[...], v_ref[...], si * split,
            len_ref[pl.program_id(0)], window, scale, m_scr, l_scr, acc_scr)

    @pl.when(si == n_splits - 1)
    def _done():
        _finalize(o_ref, l_scr, acc_scr)


def decode_attention(q, k, v, length, *, n_splits=None, window=None,
                     interpret=False):
    """q: [B,H,D]; k,v: [B,S,K,D]; attend to cache positions < length.

    ``n_splits=None`` consults the autotuner cache (fallback 8).  The
    per-sequence length is scalar-prefetched into SMEM."""
    B, H, D = q.shape
    _, S, K, _ = k.shape
    if n_splits is None:
        key = tuning.make_key("decode_attention", jax.default_backend(),
                              q.dtype, S=S, H=H, K=K, D=D, window=window or 0)
        n_splits = tuning.tuned_or_default(
            "decode_attention", key, DEFAULT_SPLITS)["n_splits"]
    n_splits = min(n_splits, S)
    while S % n_splits:
        n_splits -= 1
    split = S // n_splits
    W = K * D
    lens = jnp.full((B,), length, jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_splits),
        in_specs=[
            pl.BlockSpec((None, H, W), lambda b, s, ln: (b, 0, 0)),
            pl.BlockSpec((None, split, W), lambda b, s, ln: (b, s, 0)),
            pl.BlockSpec((None, split, W), lambda b, s, ln: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((None, H, W), lambda b, s, ln: (b, 0, 0)),
        scratch_shapes=_carry_scratch(H, W),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(D), split=split,
                          n_splits=n_splits, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, W), jnp.float32),
        interpret=interpret,
    )(lens, _expand_q(q, K), k.reshape(B, S, W), v.reshape(B, S, W))
    return _own_heads(out, K, D).astype(q.dtype)


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, page_size, n_pages,
                  window):
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        _init_carry(m_scr, l_scr, acc_scr)

    _attend(q_ref[...], k_ref[...], v_ref[...], pi * page_size,
            len_ref[pl.program_id(0)], window, scale, m_scr, l_scr, acc_scr)

    @pl.when(pi == n_pages - 1)
    def _done():
        _finalize(o_ref, l_scr, acc_scr)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           window=None, interpret=False):
    """Decode attention over a paged KV pool.

    q: [B, H, D]; k_pages, v_pages: [n_pool_pages, page_size, K, D];
    page_table: [B, n_pages] int32 indices into the pool (entries past a
    sequence's length must still be valid pool indices — use 0);
    lengths: [B] int32 live cache length per sequence.

    The page table and lengths are scalar-prefetched; the table drives the
    K/V BlockSpec index_maps so each grid step DMAs exactly one physical
    page per sequence — the virtual->physical translation costs nothing on
    the compute path.
    """
    B, H, D = q.shape
    n_pool, page_size, K = k_pages.shape[:3]
    n_pages = page_table.shape[1]
    W = K * D
    page_table = page_table.astype(jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    def page_index(b, p, pt_ref, len_ref):
        return (pt_ref[b, p], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((None, H, W), lambda b, p, pt, ln: (b, 0, 0)),
            pl.BlockSpec((None, page_size, W), page_index),
            pl.BlockSpec((None, page_size, W), page_index),
        ],
        out_specs=pl.BlockSpec((None, H, W), lambda b, p, pt, ln: (b, 0, 0)),
        scratch_shapes=_carry_scratch(H, W),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=1.0 / math.sqrt(D),
                          page_size=page_size, n_pages=n_pages,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, W), jnp.float32),
        interpret=interpret,
    )(page_table, lengths, _expand_q(q, K),
      k_pages.reshape(n_pool, page_size, W),
      v_pages.reshape(n_pool, page_size, W))
    return _own_heads(out, K, D).astype(q.dtype)


def paged_attention_pool_view(q, view, *, window=None, interpret=False):
    """Run :func:`paged_decode_attention` straight off a serving-pool view.

    ``view`` is the ``(k_pages, v_pages, page_table, lengths)`` tuple
    produced by :meth:`repro.serving.kv_pool.PagePool.kernel_view` — the
    pool's physical ``[n_pool_pages, page_size, numel]`` stores reshaped to
    the kernel's ``[n_pool_pages, page_size, K, D]`` layout with the block
    lists flattened into a padded page table.  This is the zero-copy bridge
    between the fleet allocator and the decode kernel: no gather, no dense
    materialization, the table IS the translation.
    """
    k_pages, v_pages, page_table, lengths = view
    return paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
        jnp.asarray(page_table), jnp.asarray(lengths),
        window=window, interpret=interpret)


def tune(q, k, v, length, *, window=None, trials=3,
         candidates=SPLIT_CANDIDATES, interpret=False):
    """Autotune ``n_splits`` for this cache shape; persists the winner."""
    B, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    key = tuning.make_key("decode_attention", jax.default_backend(), q.dtype,
                          S=S, H=H, K=K, D=D, window=window or 0)

    def bench(cfg):
        fn = functools.partial(decode_attention, n_splits=cfg["n_splits"],
                               window=window, interpret=interpret)
        return lambda: fn(q, k, v, length)

    cands = [{"n_splits": n} for n in candidates if n <= S]
    return tuning.autotune("decode_attention", key, cands, bench,
                           trials=trials)
