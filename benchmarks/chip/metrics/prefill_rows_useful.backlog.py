"""Sampler refill: prompt rows admitted in the window over the rows
the prefill-bearing executables computed (launches x slots, from the
device trace)."""

EXECUTABLES = ("jit__paged_refill_scan_decode", "jit__paged_prefill")


def read(run):
    if run.trace is None:
        return None
    mods = run.trace["modules"]
    launches = sum(mods[n]["count"] for n in EXECUTABLES if n in mods)
    if not launches:
        return None
    slots = int(run.deploy["slots"])
    return 100.0 * run.counters["emitted"] / (launches * slots)
