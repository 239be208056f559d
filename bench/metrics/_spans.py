"""The program's own spans in a traced run, and the device's idle time named
by them.

The program times its phases with ``repro.core.tracing.span``, which puts
each on the profiler's clock as a host event named ``<layer>.<phase>``
(``ckpt.d2h``, ``restore.read``, ``mpi.allreduce``, ...) with its ``args``
(``step``, ``restore``, ``rank``, ``bytes``, ...).  A span is kept when it
lies inside the benchmark's ``window`` span.  A program without such spans
gives an empty list, and the readers built on it return nothing.

A span here is ``Span(name, start_ns, end_ns, thread, args)``, so a test can
write a trace by hand.
"""
from __future__ import annotations

import glob
from collections import defaultdict, namedtuple

from bench import devtrace
from bench.devtrace import union_ns, window_of

#: name prefixes of the program's spans; the benchmark's own have no dot
PREFIXES = ("ckpt.", "restore.", "train.", "mpi.", "elastic.", "tier.")

Span = namedtuple("Span", "name start end thread args")


def _trace_path(run):
    paths = glob.glob(str(run.tmp / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    return paths[0] if run.trace and paths else None


def load(path):
    """(program spans, benchmark spans) of an ``.xplane.pb``'s host planes;
    the benchmark's are (name, start_ns, dur_ns) as ``devtrace`` reads them."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    program, bench = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{line.name}#{i}"
            for e in line.events:
                s, d = int(e.start_ns), int(e.duration_ns)
                if e.name.startswith(PREFIXES):
                    program.append(Span(e.name, s, s + d, thread,
                                        dict(e.stats)))
                elif e.name in devtrace.HOST_SPANS:
                    bench.append((e.name, s, d))
    return program, bench


def in_window(spans, window):
    lo, hi = window
    return [s for s in spans if lo <= s.start and s.end <= hi]


def spans(run):
    """The program's spans inside the traced window, loaded once per run."""
    cached = getattr(run, "_program_spans", None)
    if cached is None:
        path = _trace_path(run)
        program, bench = load(path) if path else ([], [])
        win = window_of(bench)
        cached = run._program_spans = in_window(program, win) if win else []
    return cached


def named(spans, name):
    return [s for s in spans if s.name == name]


def grouped(spans, name, arg):
    """The spans called ``name``, grouped by the value of ``args[arg]``."""
    out = defaultdict(list)
    for s in named(spans, name):
        out[s.args.get(arg)].append(s)
    return dict(out)


def mean_ms(durations_ns):
    if not durations_ns:
        return None
    return sum(durations_ns) / len(durations_ns) / 1e6


def read(run, value):
    """``value(spans)`` over the window's program spans, or ``None``."""
    found = spans(run)
    return value(found) if found else None


def name_gaps(device_line, program, bench, window):
    """Idle intervals of one chip in ``window``, each split among the spans
    over it: every stretch of a gap goes to the innermost (shortest)
    program span open across it, else to the innermost benchmark span
    there, else to "host" (self time: a parent span gets only what its
    children leave).  Returns [(start_ns, end_ns, {name: ns})] in time
    order, and the idle ns that no program span covers."""
    lo, hi = window
    _, busy = union_ns([(s, d) for _, s, d in device_line], lo, hi)
    gaps, prev = [], lo
    for a, b in busy + [[hi, hi]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    # (start, end, rank, name): program spans outrank the benchmark's
    todo = sorted([(s.start, s.end, 0, s.name) for s in program]
                  + [(s, s + d, 1, n) for n, s, d in bench
                     if n in devtrace.GAP_SPANS],
                  key=lambda c: c[0])
    out, uncovered, active, i = [], 0, [], 0
    for a, b in gaps:               # in time order, as is ``todo``
        while i < len(todo) and todo[i][0] < b:
            active.append(todo[i])
            i += 1
        active = [c for c in active if c[1] > a]
        over = [c for c in active if c[0] < b]
        cuts = sorted({a, b} | {t for c in over for t in c[:2] if a < t < b})
        parts = defaultdict(int)
        for x, y in zip(cuts, cuts[1:]):
            inner = min((c for c in over if c[0] <= x and y <= c[1]),
                        key=lambda c: (c[2], c[1] - c[0]), default=None)
            if inner is None or inner[2]:
                uncovered += y - x
            parts[inner[3] if inner else "host"] += y - x
        out.append((a, b, dict(parts)))
    return out, uncovered


def idle_totals(gaps):
    """{name: idle ns} over ``name_gaps``'s gaps, most first."""
    total = defaultdict(int)
    for _, _, parts in gaps:
        for name, ns in parts.items():
            total[name] += ns
    return dict(sorted(total.items(), key=lambda kv: -kv[1]))


def idle_by_span(run):
    """``name_gaps`` for chip 0 of a traced run's window, or ``None``."""
    path = _trace_path(run)
    if path is None:
        return None
    devices, bench = devtrace.load(path, run.chips)
    win = window_of(bench)
    if not devices or win is None:
        return None
    program, _ = load(path)
    return name_gaps(devices[0], program, bench, win)
