"""Input feed of each training step (ms): mean ``train.feed`` span of the
window's steps (the next batch from the prefetch queue onto the devices)."""
from bench.metrics import _spans


def value(spans):
    return _spans.mean_ms([s.end - s.start
                           for s in _spans.named(spans, "train.feed")])


def read(run):
    return _spans.read(run, value)
