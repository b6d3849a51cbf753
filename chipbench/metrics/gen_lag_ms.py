"""99th percentile of how late the generator submitted a request."""
import numpy as np


def read(run):
    return float(np.percentile(run.lag_ms, 99)) if run.lag_ms else None
