"""Median time from a request's due time to its first token on the host."""
import numpy as np


def read(run):
    return float(np.percentile(run.ttft_ms, 50)) if run.ttft_ms else None
