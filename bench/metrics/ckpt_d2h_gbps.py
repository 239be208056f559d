"""Device-to-host bandwidth (GB/s) of the window's saves: the raw bytes of
every ``ckpt.d2h`` span (one per snapshot batch, around its
``jax.device_get``) over the spans' summed durations."""
from bench.metrics import _spans


def value(spans):
    d2h = _spans.named(spans, "ckpt.d2h")
    ns = sum(s.end - s.start for s in d2h)
    return sum(s.args["bytes"] for s in d2h) / ns if ns > 0 else None


def read(run):
    return _spans.read(run, value)
