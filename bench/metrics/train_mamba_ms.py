"""Device time (ms) per window step of the Mamba-2 mixers: the train step's
operations under the ``mamba`` scope, the ``ssd`` scan inside it included
(forward, its recomputation and backward), from the trace."""
from bench.metrics._scopes import step_scope_s


def read(run):
    s = step_scope_s(run, "mamba", "ssd")
    return None if s is None else 1e3 * s
