#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control 3

For each seed it reads the numbers a run of the cell compares, for sound
runs of the program; for the first ``--control`` seeds also the control
(the reference computed in float8 e4m3, put in the program's place) and,
for training, the program with half of each batch left out.  Training's
readings need no window; serving's use a window of ``--seconds``.  One
process holds the chip throughout.  Each reading is one JSON line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common, training  # noqa: E402
from bench import run as bench_run  # noqa: E402


def half_batch(tr):
    """The fault: each step computes on half of its batch."""
    step = tr.train_step

    def broken(p, o, batch, i):
        n = batch["tokens"].shape[0] // 2
        return step(p, o, {k: v[:n] for k, v in batch.items()}, i)
    tr.train_step = broken


def train_readings(r, steps, control, fault):
    out = {}
    tr, batches = training.build(r, record=steps)
    prog = training.first_steps(r, tr, steps)
    training.close(tr)
    ref = training.reference_readings(r, batches)
    out["program"] = training.step_gaps(prog, ref)
    out["losses"] = prog["losses"]
    if control:
        ctl = training.reference_readings(r, batches, quant=True)
        out["control"] = training.step_gaps(ctl, ref)
    if fault:
        tr, _ = training.build(r, record=0)
        half_batch(tr)
        bad = training.first_steps(r, tr, steps)
        training.close(tr)
        out["half_batch"] = training.step_gaps(bad, ref)
    return out


def serve_readings(r, control):
    drv = bench_run.load_module("drivers", "serve_closed")
    drv.run(r)
    out = {"program": {c.name: c.value for c in r.checks},
           "tokens_per_s": r.values["serve_tokens_per_s"],
           "itl_p95_ms": r.values["itl_p95_ms"]}
    if control:
        out["control"] = {"served_gap": drv.served_gap(
            r.config, r.seeds["weights"], r.values["picked"],
            r.mix["max_len"], quant=True)}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_007)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    cell, config, mix, limits = common.find_cell(args.workload, spec)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("calibrate: no TPU")
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        r = bench_run.Run(args.workload, config, mix, limits, seed=seed,
                          seconds=args.seconds, trace=0,
                          devices=devices[:cell["chips"]], chips=cell["chips"])
        t0 = time.perf_counter()
        try:
            if mix["driver"] == "serve_closed":
                out = serve_readings(r, i < args.control)
            else:
                out = train_readings(r, 3, i < args.control, i < args.control)
        finally:
            r.close()
        out.update(seed=seed, seconds=time.perf_counter() - t0)
        print("READING " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
