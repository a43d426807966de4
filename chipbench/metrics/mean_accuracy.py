"""Mean declared quality of the pool member that served each request."""
import numpy as np


def read(run):
    q = run.requests["quality"][run.requests["served"]]
    return float(np.mean(q)) if len(q) else None
