"""Sampler: mean device time per launch of the fused refill + segment
executable in the window (ms)."""

EXECUTABLE = "jit__paged_refill_scan_decode"


def read(run):
    m = (run.trace or {}).get("modules", {}).get(EXECUTABLE)
    if not m or not m["count"]:
        return None
    return 1e3 * m["seconds"] / m["count"]
