"""Chunked gated-linear-attention Pallas kernels (the mLSTM / SSD hot loop).

Two schedules over the same math:

* :func:`gla_chunk` — one grid row per (batch x head); the chunk axis is the
  sequential ('arbitrary') grid dimension with the [N, P] recurrent state
  carried in VMEM scratch.  Minimal memory traffic, but the chunk axis
  serializes: wall-clock is O(nc) kernel steps per head.
* :func:`gla_chunk_parallel` — two fully-parallel Pallas phases bridged by
  an XLA ``associative_scan`` over chunks.  Phase A computes, for every
  chunk independently, the intra-chunk output plus the chunk's state delta
  (its total decay is the last cumsum entry, taken in XLA); the scan
  combines ``(g, d)`` pairs with ``(g1*g2, d2 + g2*d1)`` (decay composes
  multiplicatively, deltas decay under later gates) in O(log nc) depth;
  phase B adds each chunk's inter-chunk read of the scanned start-state.
  Use this when nc is large enough that the sequential carry, not
  bandwidth, bounds the step.

  intra-chunk:  y_i += (q_i k_j^T * exp(cum_i - cum_j))_{j<=i} v_j    (MXU)
  inter-chunk:  y_i += (q_i * exp(cum_i)) . state                      (MXU)
  state update: state = exp(total) * state + (k * exp(total - cum))^T v

Matches models/ssm.chunked_gla (the XLA production path) and is tested against
ref.naive_gla. Log-decays arrive pre-summed per chunk (cumsum done outside —
cheap VPU work that XLA fuses into the producer).

``chunk`` is a tuned knob: pass an int, or ``None`` to consult the on-disk
autotuner cache (kernels/tuning.py) with a fallback of 256.

Layout: q,k [BH, nc, c, N]; v [BH, nc, c, P]; the within-chunk inclusive
cumsum of log decay goes in twice, as a column [BH, nc, c, 1] (indexed by the
query row) and as a row [BH, nc, 1, c] (indexed by the key column), so that
every block's last two dims equal the array's and Mosaic needs no in-kernel
transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning

DEFAULT_CHUNK = {"chunk": 256}
CHUNK_CANDIDATES = (64, 128, 256, 512)


def _intra_and_delta(q, k, v, cum_col, cum_row):
    """Shared per-chunk math: intra-chunk output and the chunk's state
    delta/total decay. q,k: [c,N] f32; v: [c,P] f32; cum_col: [c,1] f32;
    cum_row: [1,c] f32 (the same cumsum, laid out as a row)."""
    c = cum_row.shape[-1]
    last = jax.lax.broadcasted_iota(jnp.int32, cum_row.shape, 1) == c - 1
    total = jnp.sum(jnp.where(last, cum_row, 0.0), axis=1, keepdims=True)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [c,c]
    ii = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    w = jnp.where(jj <= ii, jnp.exp(cum_col - cum_row), 0.0)
    y_intra = jax.lax.dot_general(s * w, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    k_scaled = k * jnp.exp(total - cum_col)
    dstate = jax.lax.dot_general(k_scaled, v, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return y_intra, dstate, total


def _inter(q, cum_col, state):
    """Each row's read of the chunk's start state: (q * exp(cum)) . state."""
    return jax.lax.dot_general(q * jnp.exp(cum_col), state,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, cc_ref, cr_ref, y_ref, state_scr):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    q = q_ref[...].astype(jnp.float32)                   # [c, N]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)                   # [c, P]
    cum_col = cc_ref[...]                                # [c, 1]
    y, dstate, total = _intra_and_delta(q, k, v, cum_col, cr_ref[...])
    state = state_scr[...]
    y = y + _inter(q, cum_col, state)
    state_scr[...] = state * jnp.exp(total) + dstate
    y_ref[...] = y.astype(y_ref.dtype)


def _phase_a_kernel(q_ref, k_ref, v_ref, cc_ref, cr_ref, y_ref, d_ref):
    """Per-chunk intra output + state delta — no cross-chunk data."""
    y, dstate, _ = _intra_and_delta(
        q_ref[...].astype(jnp.float32), k_ref[...].astype(jnp.float32),
        v_ref[...].astype(jnp.float32), cc_ref[...], cr_ref[...])
    y_ref[...] = y.astype(y_ref.dtype)
    d_ref[...] = dstate


def _phase_b_kernel(q_ref, cc_ref, state_ref, yin_ref, y_ref):
    """Add each chunk's read of its (pre-scanned) start state."""
    y = yin_ref[...].astype(jnp.float32) + _inter(
        q_ref[...].astype(jnp.float32), cc_ref[...], state_ref[...])
    y_ref[...] = y.astype(y_ref.dtype)


def _prep(q, k, v, lg, chunk):
    """Shared layout prep; resolves the chunk knob through the tuner."""
    B, S, H, N = q.shape
    P = v.shape[-1]
    if chunk is None:
        key = tuning.make_key("gla_chunk", jax.default_backend(), q.dtype,
                              S=S, H=H, N=N, P=P)
        chunk = tuning.tuned_or_default("gla_chunk", key,
                                        DEFAULT_CHUNK)["chunk"]
    c = min(chunk, S)
    while S % c:
        c //= 2
    nc = S // c

    def to_bh(x, w):
        return jnp.moveaxis(x, 2, 1).reshape(B * H, nc, c, w)

    qf = to_bh(q, N)
    kf = to_bh(k, N)
    vf = to_bh(v, P)
    # within-chunk inclusive cumsum of the log decays
    cumc = jnp.cumsum(lg.reshape(B, nc, c, H).astype(jnp.float32), axis=2)
    cumf = jnp.moveaxis(cumc, 3, 1).reshape(B * H, nc, c)
    return qf, kf, vf, cumf, (B, S, H, N, P, c, nc)


def _block(*shape):
    """BlockSpec of one (row, chunk) tile; the two leading dims squeeze."""
    return pl.BlockSpec((None, None) + shape, lambda i, j: (i, j, 0, 0))


def _from_bh(y, B, S, H, P):
    return jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)


def gla_chunk(q, k, v, lg, *, chunk=None, interpret=False):
    """q,k: [B,S,H,N]; v: [B,S,H,P]; lg: [B,S,H] log decays (<=0).
    Returns y [B,S,H,P] (final state stays device-side in the scan carry of
    the XLA path; the kernel recomputes it per call)."""
    qf, kf, vf, cumf, (B, S, H, N, P, c, nc) = _prep(q, k, v, lg, chunk)
    y = pl.pallas_call(
        _kernel,
        grid=(B * H, nc),
        in_specs=[_block(c, N), _block(c, N), _block(c, P), _block(c, 1),
                  _block(1, c)],
        out_specs=_block(c, P),
        out_shape=jax.ShapeDtypeStruct((B * H, nc, c, P), v.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, cumf[..., None], cumf[..., None, :])
    return _from_bh(y, B, S, H, P)


def gla_chunk_parallel(q, k, v, lg, *, chunk=None, interpret=False):
    """Chunk-parallel schedule of :func:`gla_chunk` — same signature, same
    numerics (both checked against ref.naive_gla)."""
    qf, kf, vf, cumf, (B, S, H, N, P, c, nc) = _prep(q, k, v, lg, chunk)
    cum_col, cum_row = cumf[..., None], cumf[..., None, :]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))

    y_intra, d = pl.pallas_call(
        _phase_a_kernel,
        grid=(B * H, nc),
        in_specs=[_block(c, N), _block(c, N), _block(c, P), _block(c, 1),
                  _block(1, c)],
        out_specs=[_block(c, P), _block(N, P)],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, nc, c, P), v.dtype),
            jax.ShapeDtypeStruct((B * H, nc, N, P), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, cum_col, cum_row)
    # each chunk's total decay is its last cumsum entry: cheap XLA work
    g = jnp.exp(cumf[..., -1])

    # inclusive scan of (decay, delta): state after chunk j given zeros
    # before chunk 0; combine is associative because decay composes
    # multiplicatively and earlier deltas decay under later gates
    def combine(a, b):
        g1, d1 = a
        g2, d2 = b
        return g1 * g2, d2 + g2[..., None, None] * d1

    _, d_inc = jax.lax.associative_scan(combine, (g, d), axis=1)
    # exclusive form: state at each chunk's START (zeros for chunk 0)
    start = jnp.concatenate(
        [jnp.zeros_like(d_inc[:, :1]), d_inc[:, :-1]], axis=1)

    y = pl.pallas_call(
        _phase_b_kernel,
        grid=(B * H, nc),
        in_specs=[_block(c, N), _block(c, 1), _block(N, P), _block(c, P)],
        out_specs=_block(c, P),
        out_shape=jax.ShapeDtypeStruct((B * H, nc, c, P), v.dtype),
        compiler_params=params,
        interpret=interpret,
    )(qf, cum_col, start, y_intra)
    return _from_bh(y, B, S, H, P)


def tune(q, k, v, lg, *, trials=3, candidates=CHUNK_CANDIDATES,
         interpret=False):
    """Autotune the chunk length for this shape; persists the winner."""
    B, S, H, N = q.shape
    P = v.shape[-1]
    key = tuning.make_key("gla_chunk", jax.default_backend(), q.dtype,
                          S=S, H=H, N=N, P=P)

    def bench(cfg):
        fn = functools.partial(gla_chunk, chunk=cfg["chunk"],
                               interpret=interpret)
        return lambda: fn(q, k, v, lg)

    cands = [{"chunk": c} for c in candidates if c <= S]
    return tuning.autotune("gla_chunk", key, cands, bench, trials=trials)
