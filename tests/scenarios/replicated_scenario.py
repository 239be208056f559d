"""Subprocess scenario: replicated-shard dedup through the full checkpoint
round trip.  8 host devices, mesh (2, 4): a fully replicated leaf has 8
addressable shards that all normalize to the same index — the snapshot
planner must store it exactly ONCE, and it must restore bit-identically on a
DIFFERENT mesh shape (4, 2)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.ckpt import CheckpointWriter, snapshot_shards
from repro.core.ckpt_pipeline import plan_snapshot
from repro.core.restore import load_arrays
from repro.launch.mesh import make_host_mesh


def main():
    assert len(jax.devices()) == 8
    mesh_a = make_host_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(7)
    replicated = jax.device_put(
        rng.normal(size=(64, 32)).astype(np.float32),
        NamedSharding(mesh_a, P()))                    # every device holds it
    sharded = jax.device_put(
        rng.normal(size=(64, 32)).astype(np.float32),
        NamedSharding(mesh_a, P("model", None)))       # 4-way, 2-way replica
    arrays = {"rep": replicated, "shard": sharded}
    world = 4

    assert len(replicated.addressable_shards) == 8
    # planner: ONE item for the replicated leaf, 4 for the 2x-replicated one
    leaves_meta, items = plan_snapshot(arrays, world, mesh_a)
    per_leaf = {}
    for it in items:
        per_leaf[it.leaf] = per_leaf.get(it.leaf, 0) + 1
    counts = sorted(per_leaf.values())
    assert counts == [1, 4], counts
    # PR 1 baseline snapshot agrees shard-for-shard with the plan
    legacy_meta, per_rank = snapshot_shards(arrays, world, mesh_a)
    assert [m["shards"] for m in legacy_meta] == \
        [m["shards"] for m in leaves_meta]
    assert sum(len(v) for v in per_rank.values()) == len(items) == 5

    # pipelined write -> restore on a DIFFERENT mesh shape, bit-identical
    with tempfile.TemporaryDirectory() as td:
        w = CheckpointWriter(Path(td), world, codec="zlib", incremental=True,
                             pipeline=True)
        w.checkpoint(1, arrays, mesh_a, {}).wait()
        ck = w.latest()
        mesh_b = make_host_mesh((4, 2), ("data", "model"))
        out = load_arrays(ck, {
            "rep": NamedSharding(mesh_b, P()),
            "shard": NamedSharding(mesh_b, P(None, "model"))})
        np.testing.assert_array_equal(np.asarray(out["rep"]),
                                      np.asarray(replicated))
        np.testing.assert_array_equal(np.asarray(out["shard"]),
                                      np.asarray(sharded))
        assert out["rep"].sharding.mesh.devices.shape == (4, 2)
        w.close()
    print("REPLICATED_SCENARIO_OK")


if __name__ == "__main__":
    main()
