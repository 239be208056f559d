"""Mean wall time (ms) of the first ``step_once`` after each restore in the
window (the trainer step re-warming on restored state), from the
benchmark's span around it."""
import numpy as np


def read(run):
    v = run.values.get("first_step_ms")
    return float(np.mean(v)) if v else None
