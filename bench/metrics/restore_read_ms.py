"""Array reads of each restore (ms): mean over the window's restores of the
first ``restore.read`` span's start to the last one's end (one span per
shard entry on the I/O pool, grouped by their ``restore`` id)."""
from bench.metrics import _spans


def value(spans):
    groups = _spans.grouped(spans, "restore.read", "restore").values()
    return _spans.mean_ms([max(s.end for s in g) - min(s.start for s in g)
                           for g in groups])


def read(run):
    return _spans.read(run, value)
