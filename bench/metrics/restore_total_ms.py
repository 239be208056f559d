"""Mean ``restart_timings["total_ms"]`` of the window's restores: the
restart layer's own span (manifest, lower half, rebind, array read and
placement)."""
import numpy as np


def read(run):
    v = run.values.get("restore_total_ms")
    return float(np.mean(v)) if v else None
