"""The interposed MPI layer's collective in each training step (ms): mean
over the window's steps of the longest ``mpi.allreduce`` span among the
ranks (the loss's world allreduce, once the loss is on the host)."""
from bench.metrics import _spans


def value(spans):
    steps = _spans.grouped(spans, "mpi.allreduce", "step").values()
    return _spans.mean_ms([max(s.end - s.start for s in g) for g in steps])


def read(run):
    return _spans.read(run, value)
