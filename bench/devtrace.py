"""From a profiler trace to the device's busy time, its idle gaps and the
operations that took the most time.

A trace is reduced from plain lists, so that a test can record one by hand:
``device_lines`` is one list of (name, start_ns, dur_ns) device-operation
events per chip, and ``host_spans`` the benchmark's own spans (name,
start_ns, dur_ns) on the same clock.  ``load`` turns the profiler's
``.xplane.pb`` into those lists.
"""
from __future__ import annotations

from collections import defaultdict

#: the benchmark's host spans that may name an idle gap, innermost first
GAP_SPANS = ("prefill", "restore", "ckpt", "step", "tick", "resume")

#: the host spans a trace is reduced from
HOST_SPANS = frozenset(GAP_SPANS + ("window",))


def union_ns(intervals, lo, hi):
    """Length of the union of [start, start + dur) intervals, clipped to
    [lo, hi), and the merged intervals themselves."""
    merged = []
    for s, d in sorted(intervals):
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def _span_at(host_spans, t):
    """Name of the innermost benchmark span open at time t, else "host"."""
    best = None
    for name, s, d in host_spans:
        if name in GAP_SPANS and s <= t < s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "host"


def reduce(device_lines, host_spans, window, top=10):
    """Busy and idle time of the devices in ``window`` = (start_ns, end_ns).

    Returns {"busy_s": mean over chips of the union of operation intervals,
    "window_s", "idle_share", "device_ops": [[name, seconds]] (most time,
    summed over chips), "idle_gaps": [[span, seconds]] (longest, on the
    first chip, named by the host span open at the gap's middle)}."""
    lo, hi = window
    if hi <= lo or not device_lines:
        return None
    busy, merged0 = [], None
    ops = defaultdict(int)
    for line in device_lines:
        total, merged = union_ns([(s, d) for _, s, d in line], lo, hi)
        busy.append(total)
        if merged0 is None:
            merged0 = merged
        for name, s, d in line:
            clipped = min(s + d, hi) - max(s, lo)
            if clipped > 0:
                ops[name] += clipped
    if not any(busy):
        return None
    gaps, prev = [], lo
    for a, b in merged0 + [[hi, hi]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    # a long window has a gap between most pairs of operations: name only
    # the longest
    gaps.sort(key=lambda g: g[0] - g[1])
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy) / 1e9
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": [[n, v / 1e9] for n, v in
                           sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_span_at(host_spans, (a + b) / 2), (b - a) / 1e9]
                          for a, b in gaps[:top]]}


def load(path, n_chips):
    """Read an ``.xplane.pb``: per-chip device operation events (the "XLA
    Ops" line of each TPU plane, named by the HLO instruction) and the
    benchmark's own host spans, as plain lists."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append([(e.name.split(" = ")[0],
                                     int(e.start_ns), int(e.duration_ns))
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events if e.name in HOST_SPANS)
    devices = [d for d in devices if d][:n_chips]
    return devices, host


def window_of(host_events, name="window"):
    """(start_ns, end_ns) of the benchmark's ``window`` span in the trace."""
    for n, s, d in host_events:
        if n == name:
            return s, s + d
    return None
