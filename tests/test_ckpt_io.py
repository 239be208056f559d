"""ckpt_io engine: codecs, chunked shard container, digests, incremental
delta chains, GC dependency protection, parallel restore, legacy v1 images,
and bf16 round-trips."""
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CkptIOConfig
from repro.core import Cluster, ckpt_io
from repro.core.ckpt import CheckpointWriter
from repro.core.restore import load_arrays, load_manifest


# ---------------------------------------------------------------------------
# codec layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec_name", ["none", "zlib"])
@pytest.mark.parametrize("arr", [
    np.arange(1000, dtype=np.float32).reshape(10, 100),
    np.zeros((513, 7), np.float64),             # compressible, odd shape
    np.array(3.5, np.float32),                  # 0-d
    np.zeros((0, 4), np.int32),                 # empty
    np.arange(5, dtype=np.int64),
    np.random.default_rng(0).normal(size=2048).astype(np.float32),  # noise
], ids=["ramp", "zeros", "scalar", "empty", "ints", "noise"])
def test_lossless_roundtrip(tmp_path, codec_name, arr):
    codec = ckpt_io.get_codec(codec_name)
    ckpt_io.write_rank_shards(tmp_path, {"x": arr}, codec, chunk_bytes=1024)
    out = ckpt_io.read_rank_entries(tmp_path, ["x"])["x"]
    assert out.dtype == arr.dtype and out.shape == arr.shape
    np.testing.assert_array_equal(out, arr)


def test_bfloat16_roundtrip_shard_container(tmp_path):
    import ml_dtypes
    arr = np.arange(37, dtype=ml_dtypes.bfloat16)
    ckpt_io.write_rank_shards(tmp_path, {"x": arr}, ckpt_io.get_codec("zlib"))
    out = ckpt_io.read_rank_entries(tmp_path, ["x"])["x"]
    assert out.dtype == np.dtype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(out.astype(np.float32),
                                  arr.astype(np.float32))


def test_int8_codec_lossy_floats_lossless_ints(tmp_path):
    rng = np.random.default_rng(1)
    f = rng.normal(size=512).astype(np.float32)
    i = rng.integers(-5, 5, 64).astype(np.int32)
    codec = ckpt_io.get_codec("int8")
    st = ckpt_io.write_rank_shards(tmp_path, {"f": f, "i": i}, codec)
    out = ckpt_io.read_rank_entries(tmp_path, ["f", "i"])
    # floats: quantized within one step of the per-tensor scale
    scale = max(np.abs(f).max(), 1e-12) / 127.0
    assert out["f"].dtype == np.float32
    np.testing.assert_allclose(out["f"], f, atol=scale * 1.01)
    # ints pass through untouched
    np.testing.assert_array_equal(out["i"], i)
    # the quantized payload is 4x smaller than the raw floats
    assert st["entries"]["f"]["nbytes"] == f.nbytes // 4


def test_lz4_codec_gated():
    try:
        import lz4.frame  # noqa: F401
        has_lz4 = True
    except ImportError:
        has_lz4 = False
    if has_lz4:
        assert ckpt_io.get_codec("lz4").name == "lz4"
    else:
        with pytest.raises(ImportError, match="lz4"):
            ckpt_io.get_codec("lz4")


def test_unknown_codec():
    with pytest.raises(KeyError, match="unknown checkpoint codec"):
        ckpt_io.get_codec("zstd-77")


def test_chunking_splits_and_reassembles(tmp_path):
    arr = np.arange(10000, dtype=np.float32)      # 40 KB over 1 KB chunks
    ckpt_io.write_rank_shards(tmp_path, {"x": arr},
                              ckpt_io.get_codec("none"), chunk_bytes=1024)
    idx = ckpt_io.read_rank_index(tmp_path)
    assert len(idx["entries"]["x"]["chunks"]) == 40
    out = ckpt_io.read_rank_entries(tmp_path, ["x"])["x"]
    np.testing.assert_array_equal(out, arr)


def test_adaptive_probe_stores_noise_raw(tmp_path):
    rng = np.random.default_rng(2)
    noise = rng.normal(size=65536).astype(np.float32)
    zeros = np.zeros(65536, np.float32)
    ckpt_io.write_rank_shards(tmp_path, {"n": noise, "z": zeros},
                              ckpt_io.get_codec("zlib"))
    idx = ckpt_io.read_rank_index(tmp_path)
    # noise fails the entropy probe -> stored raw (flag 1, enc == raw)
    n_entry = idx["entries"]["n"]
    assert all(c[2] == 1 and c[0] == c[1] for c in n_entry["chunks"])
    # zeros pass -> compressed hard
    z_entry = idx["entries"]["z"]
    assert all(c[2] == 0 for c in z_entry["chunks"])
    assert sum(c[0] for c in z_entry["chunks"]) < zeros.nbytes // 100


def test_shard_digest_qualifies_dtype_and_shape():
    a = np.arange(6, dtype=np.float32)
    assert ckpt_io.shard_digest(a) == ckpt_io.shard_digest(a.copy())
    assert ckpt_io.shard_digest(a) != ckpt_io.shard_digest(a.reshape(2, 3))
    assert ckpt_io.shard_digest(a) != ckpt_io.shard_digest(
        a.view(np.int32))
    assert ckpt_io.shard_digest(a) != ckpt_io.shard_digest(a + 1)


def test_inline_digest_matches_shard_digest(tmp_path):
    arr = np.arange(5000, dtype=np.float32)
    st = ckpt_io.write_rank_shards(tmp_path, {"x": arr},
                                   ckpt_io.get_codec("zlib"),
                                   chunk_bytes=4096, compute_digests=True)
    assert st["digests"]["x"] == ckpt_io.shard_digest(arr)


def test_resolve_dtype():
    import ml_dtypes
    assert ckpt_io.resolve_dtype("float32") == np.float32
    assert ckpt_io.resolve_dtype("bfloat16") == np.dtype(ml_dtypes.bfloat16)
    assert ckpt_io.resolve_dtype("float8_e4m3fn") == np.dtype(
        ml_dtypes.float8_e4m3fn)
    with pytest.raises(TypeError, match="cannot resolve"):
        ckpt_io.resolve_dtype("not_a_dtype")


# ---------------------------------------------------------------------------
# writer: incremental delta chains + GC
# ---------------------------------------------------------------------------

def _writer(tmp_path, **kw):
    return CheckpointWriter(tmp_path / "ck", world_size=2, **kw)


def test_incremental_second_checkpoint_writes_under_20pct(tmp_path):
    w = _writer(tmp_path, codec="zlib", incremental=True)
    arrays = {"a": jnp.asarray(np.random.default_rng(0)
                               .normal(size=(64, 64)).astype(np.float32))}
    st1 = w.checkpoint(1, arrays, None, {}).wait()
    st2 = w.checkpoint(2, arrays, None, {}).wait()
    assert st1["full"] and not st2["full"]
    assert st2["bytes_written"] < 0.2 * st1["bytes_written"]
    assert st2["fresh_shards"] == 0
    man = load_manifest(w.latest())
    assert man["base_steps"] == [1]
    out = load_arrays(w.latest(), {"a": None})
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(arrays["a"]))
    w.close()


def test_incremental_dirty_shard_rewritten(tmp_path):
    w = _writer(tmp_path, incremental=True)
    a = np.arange(16.0, dtype=np.float32)
    w.checkpoint(1, {"a": jnp.asarray(a), "b": jnp.zeros(4)}, None, {}).wait()
    st = w.checkpoint(2, {"a": jnp.asarray(a + 1), "b": jnp.zeros(4)},
                      None, {}).wait()
    assert st["fresh_shards"] == 1 and st["total_shards"] == 2
    out = load_arrays(w.latest(), {"a": None, "b": None})
    np.testing.assert_array_equal(np.asarray(out["a"]), a + 1)
    w.close()


def test_full_checkpoint_every_keep_bounds_chain(tmp_path):
    w = _writer(tmp_path, incremental=True, keep=3)
    arrays = {"a": jnp.arange(8.0)}
    fulls = []
    for step in range(1, 8):
        st = w.checkpoint(step, arrays, None, {}).wait()
        fulls.append(st["full"])
    # full at 1, then deltas until since_full reaches keep: full at 4, 7
    assert fulls == [True, False, False, True, False, False, True]
    w.close()


def test_gc_preserves_delta_dependencies(tmp_path):
    w = _writer(tmp_path, incremental=True, keep=3)
    arrays = {"a": jnp.arange(64.0)}
    for step in range(1, 6):
        w.checkpoint(step, arrays, None, {}).wait()
    names = sorted(p.name for p in w.base.iterdir())
    # keep=3 -> steps 3,4,5 kept; step 3 is a delta on the step-1 full, and
    # 5 on the step-4 full, so step 1 MUST survive GC
    assert "step_00000001" in names
    assert "step_00000002" not in names
    # every kept delta restores bit-identically
    for d in [p for p in w.base.iterdir() if (p / "COMMIT").exists()]:
        out = load_arrays(d, {"a": None})
        np.testing.assert_array_equal(np.asarray(out["a"]), np.arange(64.0))
    w.close()


def test_gc_deletes_unreferenced_when_chain_rolls_over(tmp_path):
    w = _writer(tmp_path, incremental=True, keep=2)
    arrays = {"a": jnp.arange(8.0)}
    for step in range(1, 8):
        w.checkpoint(step, arrays, None, {}).wait()
    names = {p.name for p in w.base.iterdir()}
    kept_steps = sorted(int(n.split("_")[1]) for n in names)
    # last keep=2 steps plus whatever full they depend on, nothing else
    assert 7 in kept_steps and 6 in kept_steps
    assert len(kept_steps) <= 4
    for d in sorted(w.base.iterdir()):
        man = load_manifest(d)
        for dep in man["base_steps"]:
            assert (w.base / f"step_{dep:08d}" / "COMMIT").exists()
    w.close()


def test_keep_zero_retains_everything(tmp_path):
    """Seed semantics: keep<=0 means GC never deletes."""
    w = _writer(tmp_path, keep=0)
    for step in (1, 2, 3, 4):
        w.checkpoint(step, {"x": jnp.zeros(2)}, None, {}).wait()
    commits = [p for p in w.base.iterdir() if (p / "COMMIT").exists()]
    assert len(commits) == 4
    assert w.latest().name == "step_00000004"
    w.close()


def test_cluster_conflicting_keep_rejected(tmp_path):
    with pytest.raises(ValueError, match="conflicting retention"):
        Cluster(2, "mpich", ckpt_dir=tmp_path / "ck", keep=5,
                ckpt_io=CkptIOConfig(keep=3))


def test_force_full_next(tmp_path):
    w = _writer(tmp_path, incremental=True)
    arrays = {"a": jnp.arange(8.0)}
    w.checkpoint(1, arrays, None, {}).wait()
    w.force_full_next()
    st = w.checkpoint(2, arrays, None, {}).wait()
    assert st["full"] and st["fresh_shards"] == st["total_shards"]
    w.close()


def test_latest_skips_tmp_and_uncommitted(tmp_path):
    w = _writer(tmp_path)
    w.checkpoint(1, {"a": jnp.zeros(2)}, None, {}).wait()
    # interrupted write: dir exists, no COMMIT
    broken = w.base / "step_00000009"
    broken.mkdir()
    (broken / "manifest.json").write_text("{}")
    # half-renamed tmp dir
    (w.base / "step_00000010.tmp").mkdir()
    assert w.latest().name == "step_00000001"
    assert [d.name for d in w._completed_steps()] == ["step_00000001"]
    w.close()


def test_gc_keep_semantics_ignores_tmp(tmp_path):
    w = _writer(tmp_path, keep=2)
    (w.base / "step_00000000.tmp").mkdir()
    for step in (1, 2, 3, 4):
        w.checkpoint(step, {"x": jnp.zeros(2)}, None, {}).wait()
    commits = [p.name for p in w.base.iterdir() if (p / "COMMIT").exists()]
    assert sorted(commits) == ["step_00000003", "step_00000004"]
    # .tmp dir is not GC'd (it is invisible to the scan), not counted
    assert (w.base / "step_00000000.tmp").exists()
    w.close()


# ---------------------------------------------------------------------------
# restore: parallel loader, elastic + incremental + compressed, legacy v1
# ---------------------------------------------------------------------------

def test_elastic_restart_from_incremental_compressed(tmp_path):
    cfg = CkptIOConfig(codec="zlib", incremental=True)
    cluster = Cluster(4, "craympi", ckpt_dir=tmp_path / "ck", ckpt_io=cfg)
    arrays = {"w": jnp.asarray(np.random.default_rng(3)
                               .normal(size=(32, 16)).astype(np.float32)),
              "b": jnp.arange(10, dtype=jnp.int32)}
    cluster.checkpoint(1, arrays, None).wait()
    st2 = cluster.checkpoint(2, arrays, None).wait()
    assert st2["bytes_written"] < 0.2 * max(st2["bytes_total"], 1)
    # elastic: restart the DELTA checkpoint onto a different world size
    fresh = cluster.restart(cluster.writer.latest(), new_world_size=2)
    assert fresh.world_size == 2
    out = load_arrays(cluster.writer.latest(), {"w": None, "b": None})
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(arrays["w"]))
    np.testing.assert_array_equal(np.asarray(out["b"]),
                                  np.asarray(arrays["b"]))
    # the restarted cluster's own writer starts a fresh chain: full first
    st3 = fresh.checkpoint(3, arrays, None).wait()
    assert st3["full"]


def test_bfloat16_leaf_checkpoint_restore(tmp_path):
    """Regression: np.dtype('bfloat16') raises in plain numpy; the loader
    must resolve it via ml_dtypes."""
    cluster = Cluster(2, "mpich", ckpt_dir=tmp_path / "ck")
    arr = jnp.asarray(np.arange(24, dtype=np.float32) / 8,
                      dtype=jnp.bfloat16)
    cluster.checkpoint(1, {"p": arr}, None).wait()
    out = load_arrays(cluster.writer.latest(), {"p": None})
    assert out["p"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["p"], dtype=np.float32),
                                  np.asarray(arr, dtype=np.float32))


def test_restore_parallel_workers_match_serial(tmp_path):
    w = _writer(tmp_path, codec="zlib")
    arrays = {"a": jnp.asarray(np.random.default_rng(5)
                               .normal(size=(128, 32)).astype(np.float32))}
    w.checkpoint(1, arrays, None, {}).wait()
    a1 = load_arrays(w.latest(), {"a": None}, io_workers=1)
    a4 = load_arrays(w.latest(), {"a": None}, io_workers=4)
    np.testing.assert_array_equal(np.asarray(a1["a"]), np.asarray(a4["a"]))
    w.close()


def _hand_image(base, codec_name, layout):
    """A committed 2-rank v2 image written by hand, 256-byte chunks: each
    2-D leaf is split over the two ranks along axis 0 or 1 (``layout``
    "axis0" / "axis1") or held whole by rank 0 ("full"); the scalar is
    always whole on rank 0.  Returns (step dir, manifest, original
    leaves)."""
    rng = np.random.default_rng(7)
    leaves = [rng.normal(size=(16, 96)).astype(np.float32),   # noise: raw
              np.zeros((16, 96), np.float32),      # compresses under zlib
              rng.normal(size=(8, 40)).astype(jnp.bfloat16),
              np.arange(16 * 12, dtype=np.int32).reshape(16, 12),
              np.array(2.5, np.float32)]
    step_dir = base / "step_00000001"
    per_rank, meta = {0: {}, 1: {}}, []
    for li, arr in enumerate(leaves):
        shape = list(arr.shape)
        axis = {"axis0": 0, "axis1": 1}.get(layout) if arr.ndim else None
        if axis is None:
            parts = [(0, [[0, s] for s in shape])]
        else:
            mid = shape[axis] // 2
            parts = [(r, [[lo, hi] if a == axis else [0, s]
                          for a, s in enumerate(shape)])
                     for r, (lo, hi) in enumerate([(0, mid),
                                                   (mid, shape[axis])])]
        shards = []
        for r, index in parts:
            key = f"{li}.{r}"
            per_rank[r][key] = arr[tuple(slice(a, b) for a, b in index)]
            shards.append({"rank": r, "key": key, "index": index})
        meta.append({"shape": shape, "dtype": ckpt_io.dtype_name(arr.dtype),
                     "shards": shards})
    for r, arrays in per_rank.items():
        ckpt_io.write_rank_shards(step_dir / f"rank{r:05d}", arrays,
                                  ckpt_io.get_codec(codec_name),
                                  chunk_bytes=256)
        (step_dir / f"rank{r:05d}" / "state.json").write_text("{}")
    manifest = {"format": 2, "step": 1, "world_size": 2, "mesh": None,
                "leaves": meta}
    (step_dir / "manifest.json").write_text(json.dumps(manifest))
    (step_dir / "COMMIT").write_text("ok")
    return step_dir, manifest, leaves


def _ram_source(step_dir, manifest):
    """The RAM tier's image of a committed step dir (MemoryShardReader)."""
    from repro.core.ckpt_tiers import Container, TierImage
    containers = {}
    for rdir in sorted(step_dir.glob("rank*")):
        r = int(rdir.name[len("rank"):])
        containers[(manifest["step"], r)] = Container(
            manifest["step"], r, ckpt_io.read_rank_index(rdir),
            (rdir / ckpt_io.BIN_NAME).read_bytes(), "{}", "")
    return TierImage(manifest["step"], manifest, containers)


@pytest.mark.parametrize("source", ["dir", "ram"])
@pytest.mark.parametrize("layout", ["full", "axis0", "axis1"])
@pytest.mark.parametrize("codec_name", ["none", "zlib"])
def test_array_restore_job_matches_sequential_loader(
        tmp_path, monkeypatch, codec_name, layout, source):
    """Chunk-range reads into the leaf (full cover, axis-0 shards) and the
    decode-then-copy fallback (axis-1 shards: non-contiguous slices) give
    the sequential loader's bytes, from disk and from the RAM tier."""
    from repro.core.restore import (ArrayRestoreJob, DirCheckpointSource,
                                    _load_leaves_v2_seq)
    monkeypatch.setattr(ckpt_io, "READ_SPAN_BYTES", 1024)   # 4-chunk parts
    step_dir, manifest, leaves = _hand_image(tmp_path, codec_name, layout)
    flags = {c[2] for rdir in step_dir.glob("rank*")
             for e in ckpt_io.read_rank_index(rdir)["entries"].values()
             for c in e["chunks"]}
    assert flags == ({0, 1} if codec_name == "zlib" else {1})
    src = (DirCheckpointSource(step_dir) if source == "dir"
           else _ram_source(step_dir, manifest))
    seq = _load_leaves_v2_seq(src, manifest)
    job = ArrayRestoreJob(src, manifest, [None] * len(leaves), workers=4)
    out = job.result()
    for arr, got, ref in zip(leaves, out, seq):
        got = np.asarray(got)
        assert got.dtype == ref.dtype == arr.dtype
        assert got.shape == ref.shape == arr.shape
        assert got.tobytes() == ref.tobytes() == arr.tobytes()
    # every byte lands in place unless a chunk is compressed or a slice is
    # not contiguous
    share = job.timings["read_direct_share"]
    assert (share == 1.0) == (codec_name == "none" and layout != "axis1")


@pytest.mark.parametrize("source", ["dir", "ram"])
def test_truncated_container_raises_not_partial_leaf(tmp_path, source):
    """A torn ``shards.bin`` (the fault harness truncates to 60%) fails the
    restore with IOError on the straight-into-the-leaf path too."""
    from repro.core.restore import DirCheckpointSource
    step_dir, manifest, _ = _hand_image(tmp_path, "none", "full")
    binf = step_dir / "rank00000" / ckpt_io.BIN_NAME
    binf.write_bytes(binf.read_bytes()[:int(binf.stat().st_size * 0.6)])
    src = (DirCheckpointSource(step_dir) if source == "dir"
           else _ram_source(step_dir, manifest))
    with pytest.raises(IOError, match="short read"):
        load_arrays(src, [None] * len(manifest["leaves"]))


def test_large_entry_reads_in_parts_under_one_restore_id(tmp_path,
                                                         monkeypatch):
    from repro.core import restore
    monkeypatch.setattr(ckpt_io, "READ_SPAN_BYTES", 64 << 10)
    seen = []
    real_span = restore.span

    def recording_span(name, **kw):
        seen.append((name, kw))
        return real_span(name, **kw)

    monkeypatch.setattr(restore, "span", recording_span)
    state = {"big": jnp.asarray(np.random.default_rng(1)
                                .normal(size=(256, 256)).astype(np.float32)),
             "small": jnp.ones((3, 5), jnp.bfloat16)}
    c = Cluster(1, "mpich", ckpt_dir=tmp_path / "ck",
                ckpt_io=CkptIOConfig(chunk_bytes=16 << 10))
    c.checkpoint(3, state, None).wait()
    fresh = c.restart(c.writer.latest(), new_backend="openmpi",
                      shardings={k: None for k in state})
    fresh.writer.close()
    reads = [kw for name, kw in seen if name == "restore.read"]
    by_leaf = {}
    for kw in reads:
        by_leaf.setdefault(kw["leaf"], []).append(kw)
    big, small = by_leaf[0], by_leaf[1]
    assert sorted(kw["part"] for kw in big) == [0, 1, 2, 3]
    assert sum(kw["bytes"] for kw in big) == state["big"].nbytes
    assert [(kw["part"], kw["bytes"]) for kw in small] == [
        (0, state["small"].nbytes)]
    assert {kw["restore"] for kw in reads} == {fresh.restore_id}
    np.testing.assert_array_equal(np.asarray(fresh.restored_arrays["big"]),
                                  np.asarray(state["big"]))


def test_read_pool_sized_by_host_not_world(tmp_path, monkeypatch):
    """A world-2 restore on a host with 8 usable CPUs reads with more than
    2 workers; the checkpoint writer's pool stays min(world_size, cpu)."""
    monkeypatch.setattr(ckpt_io, "usable_cpus", lambda: 8)
    monkeypatch.setattr("os.cpu_count", lambda: 8)
    state = {f"w{i}": jnp.full((64, 64), i, jnp.float32) for i in range(6)}
    c = Cluster(2, "craympi", ckpt_dir=tmp_path / "ck")
    c.checkpoint(1, state, None).wait()
    assert c.writer.io_workers == 2
    fresh = c.restart(c.writer.latest(), new_backend="exampi",
                      shardings={k: None for k in state})
    fresh.writer.close()
    assert fresh.restart_timings["read_workers"] == 6     # one per task
    assert fresh.writer.io_workers == 2
    for k, x in state.items():
        np.testing.assert_array_equal(np.asarray(fresh.restored_arrays[k]),
                                      np.asarray(x))


@pytest.mark.parametrize("codec_name", ["none", "zlib"])
def test_read_direct_share_in_restart_timings(tmp_path, codec_name):
    state = {"noise": jnp.asarray(np.random.default_rng(2)
                                  .normal(size=(64, 64)).astype(np.float32)),
             "zeros": jnp.zeros((64, 64), jnp.float32)}
    c = Cluster(2, "craympi", ckpt_dir=tmp_path / "ck",
                ckpt_io=CkptIOConfig(codec=codec_name))
    c.checkpoint(1, state, None).wait()
    fresh = c.restart(c.writer.latest(), shardings={k: None for k in state})
    fresh.writer.close()
    share = fresh.restart_timings["read_direct_share"]
    if codec_name == "none":
        assert share == 1.0
    else:                     # the zeros compress, the noise is stored raw
        assert share == 0.5


def _make_legacy_v1_ckpt(base, arrays):
    """Hand-build a seed-format (v1) checkpoint: monolithic npz per rank,
    manifest without a ``format`` field."""
    step_dir = base / "step_00000005"
    rdir = step_dir / "rank00000"
    rdir.mkdir(parents=True)
    leaves, _ = jax.tree.flatten(arrays)
    per_rank = {}
    leaves_meta = []
    for li, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        key = f"{li}.0"
        per_rank[key] = arr
        leaves_meta.append({
            "shape": list(arr.shape), "dtype": ckpt_io.dtype_name(arr.dtype),
            "shards": [{"rank": 0, "key": key,
                        "file": "rank00000/arrays.npz",
                        "index": [[0, s] for s in arr.shape]}]})
    np.savez(rdir / "arrays.npz", **per_rank)
    (rdir / "state.json").write_text("{}")
    (step_dir / "manifest.json").write_text(json.dumps({
        "step": 5, "world_size": 1, "mesh": None, "leaves": leaves_meta}))
    (step_dir / "COMMIT").write_text("ok")
    return step_dir


def test_legacy_v1_npz_checkpoint_still_loads(tmp_path):
    arrays = {"a": jnp.arange(12.0).reshape(3, 4),
              "b": jnp.ones((5,), jnp.int32)}
    ck = _make_legacy_v1_ckpt(tmp_path, arrays)
    out = load_arrays(ck, jax.tree.map(lambda x: None, arrays))
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(arrays["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]),
                                  np.asarray(arrays["b"]))


def test_npz_cache_bounded_and_closed(tmp_path):
    from repro.core.restore import _NpzCache
    paths = []
    for i in range(6):
        p = tmp_path / f"f{i}.npz"
        np.savez(p, x=np.arange(4))
        paths.append(p)
    cache = _NpzCache(cap=2)
    handles = [cache.get(p) for p in paths]
    # only cap handles stay open; evicted ones are closed
    assert len(cache._od) == 2
    closed = 0
    for h in handles[:-2]:
        try:
            h["x"]
        except Exception:  # noqa: BLE001
            closed += 1
    assert closed == 4
    cache.close()
    assert len(cache._od) == 0


def test_corrupt_shard_file_fails_loud(tmp_path):
    w = _writer(tmp_path, codec="zlib")
    w.checkpoint(1, {"a": jnp.zeros((512,))}, None, {}).wait()
    ck = w.latest()
    binf = ck / "rank00000" / ckpt_io.BIN_NAME
    binf.write_bytes(binf.read_bytes()[:10])   # truncate
    with pytest.raises(Exception):
        load_arrays(ck, {"a": None})
    w.close()


def test_write_error_surfaces_on_wait(tmp_path):
    w = _writer(tmp_path)
    req = w.checkpoint(1, {"a": jnp.zeros(2)}, None, {})
    req.wait()
    # make the base dir unwritable-ish by replacing it with a file
    shutil.rmtree(w.base)
    w.base.write_text("not a dir")
    req2 = w.checkpoint(2, {"a": jnp.zeros(2)}, None, {})
    with pytest.raises(Exception):
        req2.wait()


# ---------------------------------------------------------------------------
# crash-atomicity: kill-mid-append + torn index publish (chaos hardening)
# ---------------------------------------------------------------------------

def test_kill_mid_append_leaves_previous_ckpt_resumable(tmp_path):
    """A process death inside RankShardWriter.add (the ckpt_io.append
    failpoint) must never poison resume: the half-written step stays
    uncommitted and resume-from-latest lands on the previous good one."""
    from repro.core import faults
    from repro.core.restore import find_resumable

    w = _writer(tmp_path, codec="zlib", incremental=True)
    arrays = {"a": jnp.asarray(np.arange(4096, dtype=np.float32)),
              "b": jnp.asarray(np.ones((64, 8), np.float32))}
    w.checkpoint(1, arrays, None, {}).wait()
    good = w.latest()

    calls = []

    def die_on_second(name, ctx):
        calls.append(ctx["key"])
        if len(calls) >= 2:
            raise faults.InjectedFault("kill mid-append")

    faults.arm("ckpt_io.append", die_on_second)
    try:
        arrays2 = {k: v + 1 for k, v in arrays.items()}
        req = w.checkpoint(2, arrays2, None, {})
        with pytest.raises(Exception):
            req.wait()
    finally:
        faults.disarm("ckpt_io.append")
    # the failed step never published: no COMMIT, invisible to scans
    assert w.latest() == good
    assert find_resumable(tmp_path / "ck") == good
    out = load_arrays(good, {"a": None, "b": None})
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(arrays["a"]))
    w.close()


def test_index_publish_is_atomic(tmp_path):
    """finish() publishes index.json via tmp + os.replace: no .tmp residue,
    and a handler dying between container writes and finish leaves NO
    index at all (unreadable dir) rather than a torn one."""
    codec = ckpt_io.get_codec("zlib")
    w = ckpt_io.RankShardWriter(tmp_path / "r0", codec)
    w.add("x", np.arange(100, dtype=np.float32))
    st = w.finish()
    assert (tmp_path / "r0" / ckpt_io.INDEX_NAME).exists()
    assert not (tmp_path / "r0" / (ckpt_io.INDEX_NAME + ".tmp")).exists()
    assert ckpt_io.read_rank_index(tmp_path / "r0")["entries"].keys() \
        == st["entries"].keys()


def test_atomic_write_text_replaces_not_truncates(tmp_path):
    p = tmp_path / "f.json"
    p.write_text("old")
    ckpt_io.atomic_write_text(p, "new contents")
    assert p.read_text() == "new contents"
    assert not p.with_name(p.name + ".tmp").exists()
