"""Restart-side benchmark: the paper's §6.5 restart measurements plus the
cross-backend promise (§9) as a gate.

Two cells, mirroring bench_ckpt's write-path before/after:

  * **parallel restore A/B** — identical v2 checkpoint restored through the
    sequential loader (``load_arrays(parallel=False)``: same format, same
    group plan, zero threads) vs the parallel engine (``ArrayRestoreJob``:
    chunk-range tasks over shared positioned-read readers, decoded into
    the leaves on a pool sized by the host's CPUs).  Alternating trials, median of each, speedup gated in
    ``--smoke``;
  * **backend-pair restart matrix** — checkpoint under EVERY flavor,
    restart under every flavor (all ordered pairs incl. self), asserting
    restored param/optimizer equality byte-for-byte (sha256 of each
    restored leaf against the source arrays), live handle translation
    (comm/dtype queries through OLD handle values), and drained-message
    redelivery.  Any pair failing flips the smoke gate.

``--smoke`` writes ``BENCH_restart.json`` and exits non-zero on any gate
failure, so CI enforces the restart-path trajectory the way it already
enforces the write path.
"""
from __future__ import annotations

import hashlib
import json
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

RESTORE_SPEEDUP_GATE = 1.3


# ---------------------------------------------------------------------------
# parallel restore A/B
# ---------------------------------------------------------------------------

def _build_checkpoint(base: Path, world: int = 4, scale: int = 16) -> Path:
    """One committed v2 checkpoint with a realistic byte mix: low-entropy
    token ids and zeroed optimizer moments (compressed on disk — restore
    pays zlib) plus float noise (stored raw — restore pays pread+memcpy)."""
    import jax.numpy as jnp

    from repro.core.ckpt import CheckpointWriter

    rng = np.random.default_rng(0)
    arrays = {}
    for i in range(scale):
        arrays[f"tok{i}"] = jnp.asarray(
            rng.integers(0, 255, (1 << 20,)).astype(np.int32))
        arrays[f"mom{i}"] = jnp.zeros((1 << 19,), jnp.float32)
        arrays[f"noise{i}"] = jnp.asarray(
            rng.normal(size=(1 << 18,)).astype(np.float32))
    w = CheckpointWriter(base, world, codec="zlib", pipeline=True)
    try:
        w.checkpoint(1, arrays, None, {r: {} for r in range(world)}).wait()
        ck = w.latest()
    finally:
        w.close()
    return ck


def restore_ab(ck: Path, trials: int = 5) -> dict:
    """Best-of-alternating-trials A/B of sequential vs parallel restore
    over the SAME checkpoint (plus one unmeasured warm-up round: page
    cache, pool threads); equality-checks the two results once.

    Best-of (timeit methodology) rather than median: on small shared
    runners the noise is one-sided — a neighbor can only make a trial
    SLOWER — so each cell's minimum is its least-contended measurement and
    the ratio of minima is the stablest honest estimate of the speedup."""
    from repro.core.restore import load_arrays, load_manifest

    manifest = load_manifest(ck)
    sh = {meta_key: None for meta_key in _leaf_keys(ck)}
    samples = {"sequential": [], "parallel": []}
    outs = {}
    for i in range(trials + 1):
        for name, par in (("sequential", False), ("parallel", True)):
            t0 = time.perf_counter()
            outs[name] = load_arrays(ck, sh, parallel=par)
            if i > 0:        # round 0 warms the page cache for both cells
                samples[name].append(time.perf_counter() - t0)
    match = all(np.array_equal(np.asarray(outs["sequential"][k]),
                               np.asarray(outs["parallel"][k]))
                for k in sh)
    best = {k: min(v) for k, v in samples.items()}
    return {"sequential_s": round(best["sequential"], 4),
            "parallel_s": round(best["parallel"], 4),
            "restore_speedup": best["sequential"] / max(best["parallel"],
                                                        1e-9),
            "sequential_trials_s": [round(s, 4)
                                    for s in samples["sequential"]],
            "parallel_trials_s": [round(s, 4) for s in samples["parallel"]],
            "bytes_total": manifest["bytes_total"],
            "bytes_written": manifest["bytes_written"],
            "results_match": match,
            "trials": trials}


def _leaf_keys(ck: Path) -> list:
    # the A/B builds its checkpoint from a flat dict: leaf order == key order
    from repro.core.restore import load_manifest
    n = len(load_manifest(ck)["leaves"])
    return [k for i in range(n // 3)
            for k in (f"mom{i}", f"noise{i}", f"tok{i}")]


# ---------------------------------------------------------------------------
# backend-pair restart matrix
# ---------------------------------------------------------------------------

def _split_all(cluster, color_fn):
    out = [None] * cluster.world_size

    def run(r):
        m = cluster.mana(r)
        out[r] = m.comm_split(m.comm_world(), color_fn(r), r)

    ts = [threading.Thread(target=run, args=(r,))
          for r in range(cluster.world_size)]
    [t.start() for t in ts]
    [t.join(timeout=30) for t in ts]
    return out


def _digest_tree(tree) -> dict:
    import jax
    return {i: hashlib.sha256(
        np.ascontiguousarray(np.asarray(leaf)).tobytes()).hexdigest()[:16]
        for i, leaf in enumerate(jax.tree.leaves(tree))}


def cross_backend_matrix(world: int = 4) -> dict:
    """Checkpoint under each flavor, restart under every flavor.  Returns
    per-pair outcomes; ``ok`` is the AND over all ordered pairs."""
    import jax.numpy as jnp

    from repro.core import BACKENDS, Cluster

    rng = np.random.default_rng(1)
    arrays = {"params": jnp.asarray(rng.normal(size=(64, 32))
                                    .astype(np.float32)),
              "opt": {"m": jnp.zeros((64, 32), jnp.float32),
                      "step": jnp.asarray(np.int32(7))}}
    want = _digest_tree(arrays)
    shardings = {"params": None, "opt": {"m": None, "step": None}}
    pairs = {}
    ok = True
    for src in BACKENDS:
        with tempfile.TemporaryDirectory() as td:
            c = Cluster(world, src, ckpt_dir=Path(td) / "ck")
            subs = _split_all(c, lambda r: r % 2)
            m0 = c.mana(0)
            t = m0.type_vector(3, 2, 8, m0.dtype_handles["MPI_INT32_T"])
            c.mana(world - 1).isend(0, tag=9, payload={"inflight": src})
            c.checkpoint(1, arrays, None).wait()
            ck = c.writer.latest()
            for dst in BACKENDS:
                cell = {"ok": True}
                fresh = None
                try:
                    fresh = c.restart(ck, new_backend=dst,
                                      shardings=shardings)
                    got = _digest_tree(fresh.restored_arrays)
                    cell["digest_match"] = got == want
                    f0 = fresh.mana(0)
                    cell["handles_ok"] = (
                        f0.comm_size(subs[0]) == world // 2
                        and f0.type_envelope(t)["combiner"] == "vector"
                        and f0.recv(world - 1, 9) == {"inflight": src})
                    cell["rebind"] = {
                        k: fresh.rebind_stats[0][k]
                        for k in ("replayed", "serialized", "lazy",
                                  "reencoded_envelopes")}
                    cell["rebind_ms"] = fresh.restart_timings["rebind_ms"]
                    cell["arrays_ms"] = fresh.restart_timings["arrays_ms"]
                    cell["ok"] = cell["digest_match"] and cell["handles_ok"]
                except Exception as e:  # noqa: BLE001
                    cell = {"ok": False, "error": repr(e)}
                finally:
                    # each restart builds a fresh cluster with its own
                    # writer; release it so 25 pairs don't accumulate state
                    if fresh is not None and fresh.writer is not None:
                        fresh.writer.close()
                pairs[f"{src}->{dst}"] = cell
                ok = ok and cell["ok"]
    return {"ok": ok, "pairs": pairs,
            "world": world, "n_pairs": len(pairs)}


# ---------------------------------------------------------------------------
# harness plumbing
# ---------------------------------------------------------------------------

def smoke() -> dict:
    with tempfile.TemporaryDirectory() as td:
        ck = _build_checkpoint(Path(td) / "ab")
        ab = restore_ab(ck)
    matrix = cross_backend_matrix()
    return {"restore_ab": ab, "matrix": matrix}


def rows():
    """CSV rows for benchmarks/run.py main mode."""
    res = smoke()
    ab, mx = res["restore_ab"], res["matrix"]
    yield ("restart_restore_sequential", ab["sequential_s"] * 1e6,
           f"bytes={ab['bytes_total']}")
    yield ("restart_restore_parallel", ab["parallel_s"] * 1e6,
           f"speedup={ab['restore_speedup']:.2f}x;"
           f"match={ab['results_match']}")
    yield ("restart_matrix", float(mx["n_pairs"]),
           f"ok={mx['ok']};world={mx['world']}")


def main(out_path: str) -> None:
    res = smoke()
    with open(out_path, "w") as f:
        json.dump({"bench": "restart_smoke", "results": res}, f, indent=2)
    ab, mx = res["restore_ab"], res["matrix"]
    print(f"restart_smoke: restore_speedup={ab['restore_speedup']:.2f}x "
          f"(seq {ab['sequential_s']:.3f}s -> par {ab['parallel_s']:.3f}s) "
          f"results_match={ab['results_match']} "
          f"matrix_ok={mx['ok']} over {mx['n_pairs']} pairs", flush=True)
    ok = True
    if ab["restore_speedup"] < RESTORE_SPEEDUP_GATE:
        print(f"GATE FAILED: restore_speedup {ab['restore_speedup']:.2f}x "
              f"< {RESTORE_SPEEDUP_GATE}x", flush=True)
        ok = False
    if not ab["results_match"]:
        print("GATE FAILED: parallel restore diverges from sequential",
              flush=True)
        ok = False
    if not mx["ok"]:
        bad = [p for p, cell in mx["pairs"].items() if not cell["ok"]]
        print(f"GATE FAILED: restart matrix pairs {bad}", flush=True)
        ok = False
    print(f"wrote {out_path}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="run gates and write the json payload")
    ap.add_argument("--out", default="BENCH_restart.json")
    args = ap.parse_args()
    if args.smoke:
        main(args.out)
    else:
        for name, us, extra in rows():
            print(f"{name},{us:.1f},{extra}")
