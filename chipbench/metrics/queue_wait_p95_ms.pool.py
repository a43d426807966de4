"""Executor layer: 95th percentile of the wait from a request's due time
to the start of ``PoolExecutor.execute`` (benchmark host clock)."""
import numpy as np


def read(run):
    return float(np.percentile(run.requests["queue_wait_ms"], 95))
