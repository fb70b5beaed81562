"""Load generator: 99th percentile of how late each request was sent after
it was due, in milliseconds."""
import numpy as np


def read(run):
    lag = run.facts.get("gen_lag_s")
    if lag is None or len(lag) == 0:
        return None
    return float(np.percentile(lag, 99)) * 1e3
