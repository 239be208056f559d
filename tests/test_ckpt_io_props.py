"""Property-based round-trip coverage for the ckpt_io codec layer over
adversarial runtime-state payloads: 0-d leaves, bf16/float8 dtypes, empty
caches, and multi-chunk entries — byte-identity and digest stability must
hold across every lossless codec."""
import tempfile
from pathlib import Path

import numpy as np
import pytest as _pytest

_pytest.importorskip("hypothesis")  # optional dep: skip, not error
from hypothesis import HealthCheck, example, given, settings, strategies as st

from repro.core import ckpt_io


def _lz4_available() -> bool:
    try:
        import lz4.frame  # noqa: F401
        return True
    except ImportError:
        return False


#: every lossless codec installed — byte-identity must hold on all of them
CODECS = ["none", "zlib"] + (["lz4"] if _lz4_available() else [])

#: runtime-state-shaped dtypes: KV/recurrent caches (f32/bf16/f8), RNG key
#: data (uint32), token cursors (int32), quantized caches (int8)
DTYPES = ["float32", "float64", "int8", "uint8", "int32", "uint32",
          "bfloat16", "float8_e4m3fn"]

#: 0-d, empty, single-element, and >1-chunk shapes (chunk_bytes below is 97,
#: so 257 f32 elements stream as 11 chunks)
SHAPES = [(), (0,), (1,), (3, 2), (257,), (33, 7)]

CHUNK_BYTES = 97


@st.composite
def payloads(draw):
    dtype = ckpt_io.resolve_dtype(draw(st.sampled_from(DTYPES)))
    shape = draw(st.sampled_from(SHAPES))
    n = int(np.prod(shape, dtype=np.int64))
    seed = draw(st.integers(0, 2**32 - 1))
    raw = np.random.RandomState(seed).bytes(n * dtype.itemsize)
    return np.frombuffer(raw, np.uint8).view(dtype).reshape(shape).copy()


def _write_read(arr, codec_name):
    codec = ckpt_io.get_codec(codec_name)
    with tempfile.TemporaryDirectory() as td:
        rdir = Path(td) / "rank00000"
        stats = ckpt_io.write_rank_shards(rdir, {"0.0": arr}, codec,
                                          chunk_bytes=CHUNK_BYTES,
                                          compute_digests=True)
        with ckpt_io.RankShardReader(rdir) as rd:
            entry = rd.entry("0.0")
            out = np.array(rd.read("0.0"))   # copy out of the mmap'd view
    return stats, entry, out


@settings(max_examples=40, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(arr=payloads())
def test_roundtrip_byte_identity_and_digest_stability(arr):
    want = arr.tobytes()
    want_digest = ckpt_io.shard_digest(arr)
    for codec_name in CODECS:
        stats, entry, out = _write_read(arr, codec_name)
        assert out.dtype == arr.dtype and out.shape == arr.shape, \
            f"{codec_name}: dtype/shape mangled"
        assert out.tobytes() == want, f"{codec_name}: bytes diverged"
        # digest is over the RAW content — identical whatever the codec,
        # and the writer's fused inline hash must agree with shard_digest
        assert entry["digest"] == want_digest, \
            f"{codec_name}: digest not stable"
        assert stats["digests"]["0.0"] == want_digest
        # multi-chunk entries really are multi-chunk
        if arr.nbytes > CHUNK_BYTES:
            assert len(entry["chunks"]) > 1


@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(arr=payloads(), seed=st.integers(0, 2**31 - 1))
@example(arr=np.array(-1.9647697e-31, np.float32), seed=0)   # a 0-d leaf
def test_distinct_payloads_get_distinct_digests(arr, seed):
    other = arr.copy()
    if other.size:
        # reshape first: NumPy refuses a byte view of a 0-d array
        flat = other.reshape(-1).view(np.uint8)
        flat[seed % flat.size] ^= 0xFF
        if other.tobytes() != arr.tobytes():
            assert ckpt_io.shard_digest(other) != ckpt_io.shard_digest(arr)
    # dtype/shape-qualified: same bytes under another dtype != same digest
    if arr.dtype == np.float32 and arr.size:
        assert ckpt_io.shard_digest(arr.view(np.int32)) != \
            ckpt_io.shard_digest(arr)


def test_empty_cache_container_roundtrip():
    """An empty runtime snapshot (no decoded tokens yet, caches=None) writes
    an entry-less container that parses and reads back clean."""
    for codec_name in CODECS:
        codec = ckpt_io.get_codec(codec_name)
        with tempfile.TemporaryDirectory() as td:
            rdir = Path(td) / "rank00000"
            stats = ckpt_io.write_rank_shards(rdir, {}, codec,
                                              chunk_bytes=CHUNK_BYTES)
            assert stats["entries"] == {} and stats["raw_bytes"] == 0
            index = ckpt_io.read_rank_index(rdir)
            assert index["entries"] == {}
            assert (rdir / ckpt_io.BIN_NAME).exists()
