#!/usr/bin/env python3
"""Where a traced run's time went, by the program's own spans.

    python3 bench/span_report.py --out <report.json> -- \\
        --workload <cell> --seed <n> --seconds <s> --trace 1

Runs the cell once, as ``bench/run.py`` does with the same arguments (same
output, same result line), and reduces the run's trace before the run's
temporary directory goes:

- ``spans``: the program's spans inside the window, per name n, mean and
  summed ms;
- ``idle``: chip 0's idle time split among the spans over it
  (``_spans.name_gaps``): the longest gaps with their split, the totals by
  span, and the idle time under no program span;
- ``after_restore``: per ``train.restore``, the wait from its end to the
  device's first operation, and the device's busy time until the next
  step's loss is on the host;
- ``scopes`` (training cells): the train step's device time by
  ``jax.named_scope``, each operation counted by its own time (a loop holds
  its body's operations) and named by the innermost scope in its HLO
  ``op_name``: one of ``SCOPES`` or of the architecture's own ``SCOPES``.

The report goes to ``--out``; its headline numbers are printed as one line.
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import glob
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import common, devtrace, training  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.metrics import _spans  # noqa: E402

#: the device scopes every architecture's model and the step name
#: (``jax.named_scope``); an architecture adds its own
SCOPES = ("embed", "attention", "mlp", "moe", "norm", "head", "loss",
          "optimizer")
TOP = 10


def exclusive_ns(events):
    """{name: ns of its own} over one line's (name, start, dur) events: an
    event's time less that of the events nested inside it."""
    own, stack = defaultdict(int), []
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        own[name] += d
        if stack:
            own[stack[-1][1]] -= d
        stack.append((s + d, name))
    return own


def scope_of(op_name, scopes):
    """The innermost of ``scopes`` in an HLO ``op_name``, else "other"."""
    for part in reversed(re.split(r"[/()]", op_name or "")):
        if part in scopes:
            return part
    return "other"


def op_names(hlo_text):
    """{instruction: op_name} of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r'\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def device_lines(path, window, module):
    """Chip 0's operations in ``window`` as (name, start, dur), and those of
    them inside a run of ``module`` (by the plane's "XLA Modules" line;
    every operation where the trace has no such line)."""
    import jax
    lo, hi = window
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/device:TPU:0") or "Core" in plane.name:
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        ops = [(e.name.split(" = ")[0], int(e.start_ns), int(e.duration_ns))
               for e in lines["XLA Ops"].events
               if lo <= int(e.start_ns) < hi]
        if "XLA Modules" not in lines:
            return ops, ops
        runs = sorted((int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                      for e in lines["XLA Modules"].events
                      if e.name.startswith(module))
        starts = [a for a, _ in runs]

        def inside(t):
            i = bisect.bisect_right(starts, t) - 1
            return i >= 0 and t < runs[i][1]
        return ops, [o for o in ops if inside(o[1])]
    return [], []


def scope_table(ops, names, scopes):
    """{scope: seconds} of ``ops`` by their own time, named by ``scopes``."""
    out = defaultdict(float)
    for op, ns in exclusive_ns(ops).items():
        out[scope_of(names.get(op.lstrip("%")), scopes)] += ns / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def after_restores(program, ops):
    """Per ``train.restore``: ms from its end to the device's first
    operation, to the next step's loss on the host, and the device's busy
    ms in between."""
    ops = sorted(ops, key=lambda o: o[1])
    starts = [o[1] for o in ops]
    loss = sorted(s.end for s in _spans.named(program, "mpi.allreduce"))
    rows = []
    for r in sorted(_spans.named(program, "train.restore"),
                    key=lambda s: s.start):
        i = bisect.bisect_right(loss, r.end)
        j = bisect.bisect_left(starts, r.end)
        if i == len(loss) or j == len(ops):
            continue
        busy, _ = devtrace.union_ns([(s, d) for _, s, d in ops[j:]],
                                    r.end, loss[i])
        rows.append({"restore": r.args.get("restore"),
                     "first_op_ms": (ops[j][1] - r.end) / 1e6,
                     "loss_on_host_ms": (loss[i] - r.end) / 1e6,
                     "busy_ms": busy / 1e6})
    return rows


def reduce(run, names):
    """The report of a traced run (see the module's docstring)."""
    paths = glob.glob(str(run.tmp / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return {}
    program, bench = _spans.load(paths[0])
    win = devtrace.window_of(bench)
    if win is None:
        return {}
    inside = _spans.in_window(program, win)
    per = defaultdict(list)
    for s in inside:
        per[s.name].append((s.end - s.start) / 1e6)
    out = {"window_s": (win[1] - win[0]) / 1e9, "n_spans": len(inside),
           "spans": {n: {"n": len(v), "mean_ms": sum(v) / len(v),
                         "sum_ms": sum(v)} for n, v in sorted(per.items())}}
    ops, module_ops = device_lines(paths[0], win, "jit_train_step")
    if not ops:
        return out
    gaps, uncovered = _spans.name_gaps(ops, program, bench, win)
    idle = sum(b - a for a, b, _ in gaps)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    out["idle"] = {
        "idle_s": idle / 1e9, "uncovered_s": uncovered / 1e9,
        "uncovered_share": uncovered / idle if idle else None,
        "by_span_s": {n: ns / 1e9
                      for n, ns in _spans.idle_totals(gaps).items()},
        "longest": [{"at_s": (a - win[0]) / 1e9, "s": (b - a) / 1e9,
                     "by_span_s": {n: ns / 1e9 for n, ns in parts.items()}}
                    for a, b, parts in longest]}
    out["after_restore"] = after_restores(inside, ops)
    if names:
        scopes = SCOPES + common.arch(run.config).SCOPES
        out["scopes"] = {"train_step_s": scope_table(module_ops, names,
                                                     scopes),
                         "other_programs_s": sum(
                             exclusive_ns(ops).values()) / 1e9
                         - sum(exclusive_ns(module_ops).values()) / 1e9}
    return out


@contextlib.contextmanager
def reporting(report, training_cell):
    """Fill ``report`` from every traced run of a cell that finishes inside
    the block: its trace is reduced just before the run removes it."""
    names = {}
    close_trainer, close_run = training.close, bench_run.Run.close

    def keep_op_names(tr):
        # the compiled step's op -> op_name map, read before its state goes
        if training_cell and tr.params is not None:
            import jax.numpy as jnp
            batch = tr._device_batch(tr.pipeline.next())
            names.update(op_names(tr.train_step.lower(
                tr.params, tr.opt_state, batch,
                jnp.int32(tr.step)).compile().as_text()))
        close_trainer(tr)

    def reduce_then_close(run):
        if run.trace:
            report.update(reduce(run, names))
        close_run(run)

    training.close, bench_run.Run.close = keep_op_names, reduce_then_close
    try:
        yield report
    finally:
        training.close, bench_run.Run.close = close_trainer, close_run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True, type=Path)
    ap.add_argument("run_args", nargs=argparse.REMAINDER,
                    help="-- then bench/run.py's arguments")
    args = ap.parse_args(argv)
    run_args = [a for a in args.run_args if a != "--"]
    cell = run_args[run_args.index("--workload") + 1]
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    training_cell = common.find_cell(cell, spec)[2]["driver"] == "train_ckpt"
    report = {}
    try:
        with reporting(report, training_cell):
            bench_run.main(run_args)
    finally:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(report, indent=1))
    idle = report.get("idle", {})
    print("SPAN_REPORT " + json.dumps(
        {"n_spans": report.get("n_spans"),
         "uncovered_share": idle.get("uncovered_share"),
         "idle_by_span_s": dict(list(idle.get("by_span_s", {}).items())[:6]),
         "scopes": report.get("scopes", {}).get("train_step_s")}),
        flush=True)


if __name__ == "__main__":
    main()
