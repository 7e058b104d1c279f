"""itl_p95_ms: 95th percentile of the inter-token gap, host clock.

A gap is the time between two successive decode calls of one request
(each call starts once the previous token has reached the host).  The
metric is the 95th percentile (linear interpolation) of every gap of
every request in the window, in ms.
"""
import numpy as np


def read(run):
    gaps = [g for s in run.record.values["token_stamps"] for g in np.diff(s)]
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
