"""Scheduler: 95th percentile of the queue age of the prompts emitted in
the window (``SchedulerStats.queue_ages``, ms)."""
import numpy as np


def read(run):
    ages = run.counters["queue_ages"]
    return float(np.percentile(ages, 95)) * 1e3 if len(ages) else None
