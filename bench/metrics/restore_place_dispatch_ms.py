"""Dispatch of each restore's placement (ms): mean ``restore.place`` span
of the window's restores, in which every reassembled leaf is put under its
new sharding.  The host-to-device copies land after the span, behind the
host work that follows, so this is the host's share of placement, not the
transfer."""
from bench.metrics import _spans


def value(spans):
    return _spans.mean_ms([s.end - s.start
                           for s in _spans.named(spans, "restore.place")])


def read(run):
    return _spans.read(run, value)
