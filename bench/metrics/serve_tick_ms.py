"""Mean wall time (ms) of the window's ``ServeEngine.step_once`` ticks
(admission, prefill, one decode per running session, host write-through),
from the benchmark's span around each."""
import numpy as np


def read(run):
    v = run.values.get("tick_s")
    return float(np.mean(v)) * 1e3 if v else None
