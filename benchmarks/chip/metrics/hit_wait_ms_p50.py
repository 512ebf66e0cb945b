"""Engine intake, FIFO yield: median time from pull to yield of requests
whose every pair hit the prediction cache (ms).  Its tail is the wait
behind an earlier request's misses (results leave in arrival order)."""
import numpy as np


def read(run):
    return (float(np.percentile(run.hit_wait_ms, 50))
            if len(run.hit_wait_ms) else None)
