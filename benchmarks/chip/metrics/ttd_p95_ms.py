"""95th percentile of time to decision (ms) over every counted query."""
import numpy as np


def read(run):
    return float(np.percentile(run.ttd_ms, 95)) if len(run.ttd_ms) else None
