"""Engine intake: 95th percentile of the time from a query's scheduled
arrival until the engine pulled it (ms, harness clock).  The engine pulls
once per decode segment, so this is the wait for a segment boundary."""
import numpy as np


def read(run):
    return (float(np.percentile(run.intake_ms, 95)) if len(run.intake_ms)
            else None)
