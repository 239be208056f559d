"""Shared by the ``device_idle_share.*`` readers: the share of the traced
window in which no operation ran on the device, averaged over the chips."""


def idle_share(run):
    s = run.summary
    if s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
