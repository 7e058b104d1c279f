"""train_idle_share: share of the traced window in which no operation
ran on the device, averaged over the chips, in percent."""
from chipbench import trace as tr


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s(run.trace) / run.trace.window_s)
