"""Device idle share (%) of the traced window in the train cells: 1 minus
the union of device-operation intervals over the window, from the trace."""
from bench.metrics._idle import idle_share


def read(run):
    return idle_share(run)
