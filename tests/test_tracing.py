"""Spans on the profiler's clock (``repro.core.tracing``): the helper fills
the timing dicts, and a checkpoint and a restart under the profiler leave
their per-batch and per-entry spans in the host plane, with the bytes and
ids a reader groups them by."""
from __future__ import annotations

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Cluster, tracing
from repro.core.tracing import span


def test_span_fills_into_key():
    timings = {}
    with span("t.one", into=timings, key="one_ms", step=3) as sp:
        pass
    assert timings["one_ms"] == round(sp.ms, 3) >= 0
    assert sp.t1 >= sp.t0


def test_span_add_sums_into_one_key():
    timings = {}
    parts = []
    for _ in range(3):
        with span("t.part", into=timings, key="sum_ms", add=True) as sp:
            sum(range(1000))
        parts.append(sp.ms)
    assert timings["sum_ms"] == pytest.approx(sum(parts), abs=2e-3)


def test_span_closes_its_annotation_when_the_block_raises(monkeypatch):
    events = []

    class Annotation:
        def __init__(self, name, **args):
            self.name, self.args = name, args

        def __enter__(self):
            events.append(("enter", self.name, self.args))

        def __exit__(self, *exc):
            events.append(("exit", self.name, exc[0]))

    monkeypatch.setattr(tracing, "TraceAnnotation", Annotation)
    timings = {}
    with pytest.raises(ValueError):
        with span("t.fails", into=timings, key="fails_ms", rank=1, leaf=None):
            raise ValueError("boom")
    assert events == [("enter", "t.fails", {"rank": 1}),
                      ("exit", "t.fails", ValueError)]
    assert "fails_ms" not in timings     # a failed phase records no time


def _host_spans(trace_dir, prefixes=("ckpt.", "restore.")):
    path = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, dict(e.stats)) for e in line.events
                        if e.name.startswith(prefixes)]
    return out


def test_checkpoint_and_restart_spans_in_the_trace(tmp_path):
    state = {"w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64),
             "b": jnp.ones((3, 5), jnp.bfloat16),
             "s": jnp.asarray(7, jnp.int32)}
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state))
    c = Cluster(2, "craympi", ckpt_dir=tmp_path / "ck")
    with jax.profiler.trace(str(tmp_path / "trace")):
        req = c.checkpoint(5, state, None)
        req.wait()
        fresh = c.restart(c.writer.latest(), new_backend="openmpi",
                          shardings={k: None for k in state})
    fresh.writer.close()
    spans = _host_spans(tmp_path / "trace")
    by = {}
    for name, args in spans:
        by.setdefault(name, []).append(args)

    d2h = by["ckpt.d2h"]
    assert sum(a["bytes"] for a in d2h) == nbytes
    assert all(a["step"] == 5 for a in d2h)
    assert sorted(a["batch"] for a in d2h) == list(range(len(d2h)))
    assert [a["step"] for a in by["ckpt.blocking"]] == [5]
    assert sum(a["bytes"] for a in by["ckpt.sink"]) == nbytes

    rid = "5@1"
    reads = by["restore.read"]
    assert len(reads) == len(state)
    assert sum(a["bytes"] for a in reads) == nbytes
    assert all(a["restore"] == rid for a in reads)
    assert all(a["part"] == 0 for a in reads)      # each under the span
    assert [a["bytes"] for a in by["restore.place"]] == [nbytes]
    assert [a["restore"] for a in by["restore.total"]] == [rid]
    total = by["restore.total"][0]
    assert total["read_workers"] == fresh.restart_timings["read_workers"]
    assert float(total["read_direct_share"]) == 1.0

    for k in ("drain_ms", "rank_state_ms", "snapshot_ms", "enqueue_ms",
              "blocking_ms", "persist_ms"):
        assert k in req.timings, req.timings
    assert "device_to_host_s" not in req.write_stats
    t = fresh.restart_timings
    for k in ("manifest_ms", "lower_half_ms", "rebind_ms", "read_ms",
              "place_ms", "arrays_ms", "total_ms"):
        assert k in t, t
    assert t["place_ms"] <= t["arrays_ms"] <= t["total_ms"]
    for k, x in state.items():
        np.testing.assert_array_equal(np.asarray(fresh.restored_arrays[k]),
                                      np.asarray(x))
