"""Mean wall time (ms) of the window's ``step_once`` calls, from the
benchmark's span around each; the loss reaches the host inside it."""
import numpy as np


def read(run):
    steps = run.values.get("step_s")
    return float(np.mean(steps)) * 1e3 if steps else None
