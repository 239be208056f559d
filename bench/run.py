#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``.  Its configuration file and its
traffic file are found by name; the configuration's ``model_type`` names its
architecture (``bench/archs/<model_type>.py``), the traffic file names the
driver (``bench/drivers/<driver>.py``) that runs it, and every per-layer
metric is read by ``bench/metrics/<metric>.py``.  A new architecture,
configuration, traffic mix or metric is therefore a new file, never an
edit.

The run sets up (weights from the seed, every shape the window uses
compiled or loaded from ``<checkout>/.jax_cache``), measures for
``--seconds``, then checks what the window produced against the plain
reference.  With ``--trace 0`` it prints the cell's end-to-end metrics;
with ``--trace 1`` it traces the window and prints the per-layer metrics,
the device's busy time and a breakdown.  The last line of standard output
is one JSON object; the last lines of standard error are the numbers
compared, each beside its limit.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
if str(BENCH.parent) not in sys.path:
    sys.path.insert(0, str(BENCH.parent))

from bench import common, devtrace  # noqa: E402

CACHE_DIR = common.ROOT / ".jax_cache"


def load_module(kind, name):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r} at {path}")
    return common.load_file(path, f"bench_{kind}_{name.replace('.', '_')}")


class Run:
    """One run of one cell: what the driver is given, and what it fills in
    (``values``, ``checks``, ``attempted``, ``failed``) for the metric
    readers and the result line."""

    def __init__(self, name, config, mix, limits, *, seed, seconds, trace,
                 devices, chips):
        self.name, self.config, self.mix, self.limits = name, config, mix, limits
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.chips = devices, chips
        self.seeds = common.derived_seeds(seed)
        self.rec = common.Recorder()
        self.compiles = common.CompileCounter()
        self.tmp = Path(tempfile.mkdtemp(prefix="bench_run_"))
        self.values: dict = {}
        self.checks: list = []
        self.attempted = self.failed = 0
        self.t_open = self.t_close = None
        self.device = None
        self.summary = None

    def log(self, msg):
        print(msg, flush=True)

    @contextlib.contextmanager
    def window(self):
        """The measured window: compiles inside it are counted, and with
        ``--trace 1`` the profiler records it.  Memory is read on exit."""
        import jax
        if self.trace:
            # the device's operations and the benchmark's own spans, and no
            # event per Python call: a long window would trace millions
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.tmp / "trace"),
                                     profiler_options=opts)
        self.compiles.on = True
        self.t_open = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("window"):
                yield
        finally:
            self.t_close = time.perf_counter()
            self.compiles.on = False
            if self.trace:
                jax.profiler.stop_trace()
            self.device = common.device_info(self.devices)
        self.log(f"window {self.t_close - self.t_open:.6f} s; compiles "
                 f"inside the window: {self.compiles.count}")

    def check(self, name, value, limit):
        self.checks.append(common.Check(name, float(value), float(limit)))

    def close(self):
        shutil.rmtree(self.tmp, ignore_errors=True)


def reduce_trace(run):
    paths = glob.glob(str(run.tmp / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    devices, host = devtrace.load(paths[0], run.chips)
    win = devtrace.window_of(host)
    return devtrace.reduce(devices, host, win) if win else None


def execute(spec, name, *, seed, seconds, trace, devices, root=common.ROOT):
    """Run cell ``name`` of ``spec`` (the parsed ``BENCHMARK.json`` of the
    checkout at ``root``) and return the result object."""
    cell, config, mix, limits = common.find_cell(name, spec, root)
    run = Run(name, config, mix, limits, seed=seed, seconds=seconds,
              trace=trace, devices=devices[:cell["chips"]],
              chips=cell["chips"])
    try:
        load_module("drivers", mix["driver"]).run(run)
        run.values["setup_s"] = run.t_open - T_START
        if trace:
            run.summary = reduce_trace(run)
        section = "per_layer" if trace else "end_to_end"
        units = {m["name"]: m["unit"] for m in spec[section]}
        metrics = {}
        for m in common.cell_metrics(spec, name, section):
            value = (load_module("metrics", m).read(run) if trace
                     else run.values.get(m))
            if value is not None:
                metrics[m] = {"value": value, "unit": units[m]}
        result = {"correct": bool(run.checks)
                  and all(c.ok for c in run.checks),
                  "attempted": run.attempted, "failed": run.failed,
                  "metrics": metrics, "device": run.device}
        if trace and run.summary:
            result["device"]["busy_s"] = run.summary["busy_s"]
            result["device"]["window_s"] = run.summary["window_s"]
            result["breakdown"] = {"device_ops": run.summary["device_ops"],
                                   "idle_gaps": run.summary["idle_gaps"]}
        result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                            for c in run.checks}
        return result
    finally:
        run.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    cell, _, _, _ = common.find_cell(args.workload, spec)

    # the persistent compilation cache lives at a fixed path in the
    # checkout, and every program is cached, however quick to compile
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    d = devices[0]
    print(f"jax {jax.__version__}; {len(devices)} x {d.platform} "
          f"{d.device_kind}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {d.platform!r}); the "
                 "benchmark never runs on another platform")
    if len(devices) < cell["chips"]:
        sys.exit(f"bench: {args.workload} needs {cell['chips']} chips, "
                 f"found {len(devices)}")
    result = execute(spec, args.workload, seed=args.seed,
                     seconds=args.seconds, trace=args.trace, devices=devices)
    for n, c in result["checks"].items():
        print(f"check {n}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
