"""Median time to decision (ms): from each counted query's scheduled
arrival until ``decide`` returned its routing decision (host clock)."""
import numpy as np


def read(run):
    return float(np.percentile(run.ttd_ms, 50)) if len(run.ttd_ms) else None
