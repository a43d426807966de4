"""95th percentile of the end-to-end time of every request served in
the window: the round-trip uplink plus the time from the request's due
time to the end of its ``execute``, queue wait included."""
import numpy as np


def read(run):
    e2e = run.requests["e2e_ms"][run.requests["served"]]
    return float(np.percentile(e2e, 95)) if len(e2e) else None
