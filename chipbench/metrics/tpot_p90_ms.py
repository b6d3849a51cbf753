"""90th percentile over requests of (last token - first token) / (tokens - 1),
with the tokens each had on the host at the window's end."""
import numpy as np


def read(run):
    return float(np.percentile(run.tpot_ms, 90)) if run.tpot_ms else None
