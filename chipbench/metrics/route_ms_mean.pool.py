"""Router layer: mean host time of the executor's ``router.route`` call,
from the benchmark's span around it."""
import numpy as np


def read(run):
    d = run.spans.durations_ms("pool.route")
    return float(np.mean(d)) if d else None
