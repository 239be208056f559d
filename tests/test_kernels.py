"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret=True
executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_chunk import gla_chunk

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,S,D,window", [
    (2, 4, 2, 128, 64, None),
    (1, 4, 4, 256, 32, None),
    (2, 6, 2, 128, 128, 32),
    (1, 2, 1, 64, 96, None),       # non-MXU-aligned head dim -> padded
    (1, 8, 2, 64, 64, 16),
])
def test_flash_attention_sweep(B, H, K, S, D, window, dtype):
    ks = jax.random.split(jax.random.key(S * D + H), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, K, S, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, K, S, D), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, window=window, q_block=64, kv_block=64,
                          interpret=True)
    want = ref.naive_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,S,D,length,window", [
    (2, 4, 2, 64, 64, 50, None),
    (1, 8, 1, 128, 32, 128, None),
    (2, 4, 4, 64, 64, 33, 16),
    (1, 2, 2, 96, 128, 7, None),   # S not divisible by n_splits -> adjusted
])
def test_decode_attention_sweep(B, H, K, S, D, length, window, dtype):
    ks = jax.random.split(jax.random.key(S + D + length), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, D), jnp.float32).astype(dtype)
    out = decode_attention(q, k, v, length, n_splits=8, window=window,
                           interpret=True)
    want = ref.naive_decode_attention(q, jnp.moveaxis(k, 1, 2),
                                      jnp.moveaxis(v, 1, 2), length,
                                      window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,S,N,P,chunk", [
    (2, 3, 64, 32, 32, 16),
    (1, 2, 128, 16, 64, 32),
    (1, 1, 96, 8, 8, 32),          # S % chunk != 0 -> chunk halved
])
def test_gla_chunk_sweep(B, H, S, N, P, chunk, dtype):
    ks = jax.random.split(jax.random.key(S * N), 4)
    q = jax.random.normal(ks[0], (B, S, H, N), jnp.float32).astype(dtype)
    k = (jax.random.normal(ks[1], (B, S, H, N), jnp.float32) * 0.3).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, P), jnp.float32).astype(dtype)
    lg = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H))) * 0.3
    out = gla_chunk(q, k, v, lg, chunk=chunk, interpret=True)
    want, _ = ref.naive_gla(q, k, v, lg)
    tol = {jnp.float32: 5e-4, jnp.bfloat16: 5e-2}[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_ops_dispatch_uses_ref_on_cpu():
    from repro.kernels import ops
    B, H, K, S, D = 1, 2, 2, 32, 16
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, K, S, D))
    v = jax.random.normal(ks[2], (B, K, S, D))
    out = ops.flash_attention(q, k, v)
    want = ref.naive_attention(q, k, v)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", ["flash_attention", "decode_attention", "gla"])
def test_forced_kernel_fails_loudly_off_tpu(op):
    """Kernels default to interpret=False: forcing the Pallas path on the
    CPU raises instead of silently running the interpreter."""
    from repro.kernels import ops
    B, H, K, S, D = 1, 2, 2, 32, 16
    heads_first = jnp.zeros((B, H, S, D))       # flash layout
    seq_first = jnp.zeros((B, S, K, D))         # decode cache / GLA layout
    calls = {
        "flash_attention": lambda: ops.flash_attention(
            heads_first, heads_first, heads_first, force="kernel"),
        "decode_attention": lambda: ops.decode_attention(
            heads_first[:, :, 0], seq_first, seq_first, jnp.int32(S),
            force="kernel"),
        "gla": lambda: ops.gla(seq_first, seq_first, seq_first,
                               jnp.zeros((B, S, K)), chunk=16,
                               force="kernel"),
    }
    with pytest.raises(ValueError, match="interpret"):
        calls[op]()


# ---------------------------------------------------------------------------
# paged decode (DMA-gathered KV pool via scalar-prefetch page table)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 24])
def test_paged_decode_attention(window):
    from repro.kernels.decode_attention import paged_decode_attention
    B, H, K, D = 2, 4, 2, 64
    page_size, n_pages = 16, 4
    S = page_size * n_pages
    n_pool = B * n_pages + 3           # pool bigger than needed, shuffled
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k_pool = jax.random.normal(ks[1], (n_pool, page_size, K, D))
    v_pool = jax.random.normal(ks[2], (n_pool, page_size, K, D))
    rng = np.random.default_rng(0)
    pt = rng.permutation(n_pool)[:B * n_pages].reshape(B, n_pages)
    lengths = np.array([S - 5, 2 * page_size - 3], np.int32)
    # entries past length must stay VALID pool indices (contract: use 0)
    pt_masked = pt.copy()
    for b in range(B):
        pt_masked[b, (lengths[b] + page_size - 1) // page_size:] = 0
    out = paged_decode_attention(q, k_pool, v_pool,
                                 jnp.asarray(pt_masked, jnp.int32),
                                 jnp.asarray(lengths), window=window,
                                 interpret=True)
    for b in range(B):
        # gather the contiguous cache this page table encodes, then oracle
        kc = np.concatenate([np.asarray(k_pool[pt[b, p]])
                             for p in range(n_pages)])[None]  # [1,S,K,D]
        vc = np.concatenate([np.asarray(v_pool[pt[b, p]])
                             for p in range(n_pages)])[None]
        want = ref.naive_decode_attention(
            q[b:b + 1], jnp.moveaxis(jnp.asarray(kc), 1, 2),
            jnp.moveaxis(jnp.asarray(vc), 1, 2), int(lengths[b]),
            window=window)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                   np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# chunk-parallel GLA (associative-scan state carry)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,S,N,P,chunk", [
    (2, 3, 64, 32, 32, 16),
    (1, 2, 96, 16, 32, 32),            # S % chunk != 0 -> chunk halved
])
def test_gla_chunk_parallel_matches_oracle(B, H, S, N, P, chunk):
    from repro.kernels.mlstm_chunk import gla_chunk_parallel
    ks = jax.random.split(jax.random.key(S * N + 1), 4)
    q = jax.random.normal(ks[0], (B, S, H, N))
    k = jax.random.normal(ks[1], (B, S, H, N)) * 0.3
    v = jax.random.normal(ks[2], (B, S, H, P))
    lg = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H))) * 0.3
    out = gla_chunk_parallel(q, k, v, lg, chunk=chunk, interpret=True)
    want, _ = ref.naive_gla(q, k, v, lg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# blocked XLA fast paths (the CPU/GPU production dispatch targets)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,window,S", [
    (True, None, 128),
    (True, 32, 128),
    (False, None, 128),
    (True, None, 80),                  # S not a multiple of the q block
    (True, 17, 96),                    # odd window, odd-ish S
])
def test_xla_flash_matches_ref(causal, window, S):
    from repro.kernels import xla_fast
    B, H, K, D = 2, 4, 2, 64
    ks = jax.random.split(jax.random.key(S), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, K, S, D))
    v = jax.random.normal(ks[2], (B, K, S, D))
    out = xla_fast.flash_attention_xla(q, k, v, causal=causal, window=window,
                                       q_block=32)
    want = ref.naive_attention(q, k, v, causal=causal,
                               window=window if causal else None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("length,window", [(90, None), (64, 16), (7, None)])
def test_xla_decode_matches_ref(length, window):
    from repro.kernels import xla_fast
    B, H, K, S, D = 2, 4, 2, 96, 64
    ks = jax.random.split(jax.random.key(length), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    out = xla_fast.decode_attention_xla(q, k, v, length, window=window)
    want = ref.naive_decode_attention(q, jnp.moveaxis(k, 1, 2),
                                      jnp.moveaxis(v, 1, 2), length,
                                      window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# tuned-vs-default block resolution (the cache consult path)
# ---------------------------------------------------------------------------

def test_flash_tuned_blocks_from_cache(tmp_path, monkeypatch):
    """tune() persists a winner; a later call with block=None resolves it
    from the cache and matches both the oracle and the default-block path."""
    from repro.kernels import flash_attention as fa
    from repro.kernels import tuning
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "cache.json"))
    B, H, K, S, D = 1, 2, 2, 64, 32
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, K, S, D))
    v = jax.random.normal(ks[2], (B, K, S, D))
    default = fa.flash_attention(q, k, v, interpret=True)  # cache miss
    win = fa.tune(q, k, v, trials=1,
                  candidates=((32, 32), (64, 64)), interpret=True)
    assert {"q_block", "kv_block"} <= set(win)
    key = tuning.make_key("flash_attention", jax.default_backend(), q.dtype,
                          S=S, D=D, causal=1, window=0)
    assert tuning.lookup("flash_attention", key) is not None
    tuned = fa.flash_attention(q, k, v, interpret=True)    # cache hit
    want = ref.naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(default),
                               rtol=1e-6, atol=1e-6)
