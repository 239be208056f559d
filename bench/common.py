"""What every driver of the benchmark shares: the cell's files, its
architecture's module, seeds, host spans, the count of compiles, state
fingerprints and the checks that decide ``correct``.

Nothing here touches a device at import.  The program under test is
imported from ``<root>/src`` only where a driver needs it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
ARCHS = BENCH / "archs"
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def load_file(path, name):
    """The Python file at ``path`` as a module called ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path):
    return json.loads(Path(path).read_text())


def find_cell(name, spec=None, root=ROOT):
    """(cell entry, config file, traffic file, limits) for one cell of
    ``BENCHMARK.json`` under ``root``.  The configuration and the traffic
    are found by name, so a new cell needs only new files."""
    root = Path(root)
    spec = spec if spec is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    mix = load_json(root / "bench" / "workloads" / f"{cell['traffic']}.json")
    limits_path = root / "bench" / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.exists() else {}
    return cell, config, mix, limits


def cell_metrics(spec, cell_name, section):
    """Names of the metrics of ``section`` ("end_to_end" or "per_layer")
    that this cell reports: those that list it, or list no cells."""
    return [m["name"] for m in spec[section]
            if cell_name in m.get("workloads", [cell_name])]


def derived_seeds(seed):
    """Independent 30-bit seeds for weights, data and traffic from one
    ``--seed`` of any size (JAX keeps only 32 bits of an integer seed)."""
    words = np.random.SeedSequence(int(seed)).generate_state(3, np.uint32)
    return {k: int(w >> 2) for k, w in zip(("weights", "data", "traffic"),
                                           words)}


def arch(config):
    """The module of the configuration's architecture,
    ``bench/archs/<model_type>.py``: its program mapping, weights' layout,
    reference and model FLOPs.  An architecture is therefore a new file."""
    name = config["model_type"]
    path = ARCHS / f"{name}.py"
    if not path.exists():
        known = sorted(p.stem for p in ARCHS.glob("*.py"))
        raise KeyError(f"no architecture {name!r} at {path}; known: {known}")
    return load_file(path, f"bench_archs_{name.replace('.', '_')}")


def program_config(config):
    """The program's ``ModelConfig`` for a configuration file."""
    return arch(config).program_config(config)


class Recorder:
    """Host spans on the benchmark's own clock, mirrored into the profiler's
    trace (``TraceAnnotation``) so that a traced run can name device gaps
    by what the host was doing."""

    def __init__(self):
        self.spans = []          # (name, t0, t1), perf_counter seconds

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def durations(self, name, t_open=None, t_close=None):
        return [b - a for n, a, b in self.spans if n == name
                and (t_open is None or a >= t_open)
                and (t_close is None or b <= t_close)]


class CompileCounter:
    """Counts XLA compiles and persistent-cache lookups while it is on."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring as mon
        self.on = False
        self.count = 0
        mon.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):  # noqa: ARG002 — listener API
        if self.on and event in self.EVENTS:
            self.count += 1


def _fingerprint(leaves):
    def one(x):
        width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        w = jax.lax.bitcast_convert_type(x, width).astype(jnp.uint32)
        w = w.reshape(-1)
        i = jax.lax.iota(jnp.uint32, w.shape[0])
        a = jnp.sum((w ^ (i * jnp.uint32(0x9E3779B1))) * jnp.uint32(0x85EBCA6B),
                    dtype=jnp.uint32)
        b = jnp.sum(w * (i | jnp.uint32(1)), dtype=jnp.uint32)
        return jnp.stack([a, b])
    return jnp.stack([one(x) for x in leaves])


#: Two position-weighted 32-bit sums of each leaf's raw bits, on the device
#: in one program: equal bytes give equal fingerprints, and a changed, moved
#: or missing word changes them.  Dispatch returns at once.
fingerprint = jax.jit(_fingerprint)

#: Per-leaf float32 L2 norms of a list of arrays, on the device.
leaf_norms = jax.jit(lambda xs: jnp.stack(
    [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]))

#: Per-leaf float32 L2 norms of ``xs - ys``, on the device.
diff_norms = jax.jit(lambda xs, ys: jnp.stack(
    [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
     for x, y in zip(xs, ys)]))


def worst_leaf_gap(prog, ref, keep=None):
    """The widest gap between the program's and the reference's per-leaf
    norms, each over the larger of that leaf's reference norm and the
    median leaf's.  ``keep`` masks the leaves that count."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool) if keep is None else np.asarray(keep)
    denom = np.maximum(ref, np.median(ref[keep]))
    return float(np.max(np.abs(prog - ref)[keep] / denom[keep]))


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit; ``ok`` when at most it."""
    name: str
    value: float
    limit: float

    @property
    def ok(self):
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def device_info(devices):
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
