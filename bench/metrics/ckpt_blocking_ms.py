"""Mean stall (ms) of the window's saves: the checkpoint plane's own
``req.timings["blocking_ms"]`` (drain, snapshot, enqueue)."""
import numpy as np


def read(run):
    v = run.values.get("ckpt_blocking_ms")
    return float(np.mean(v)) if v else None
