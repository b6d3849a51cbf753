"""90th percentile of time from due to first token on the host; a request
without a first token at the window's end enters at its age."""
import numpy as np


def read(run):
    return float(np.percentile(run.ttft_ms, 90)) if run.ttft_ms else None
