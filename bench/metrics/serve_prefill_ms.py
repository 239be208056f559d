"""Mean wall time (ms) of the window's prefill calls, from the benchmark's
span around the engine's ``prefill_fn``, ended on a blocked result."""
import numpy as np


def read(run):
    v = run.values.get("prefill_s")
    return float(np.mean(v)) * 1e3 if v else None
