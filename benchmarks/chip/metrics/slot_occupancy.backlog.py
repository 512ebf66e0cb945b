"""Slot runtime: share of decode slot-steps that served a live request,
over the window (the slot states' counters, as ``SchedulerStats``)."""


def read(run):
    c = run.counters
    if not c["slot_steps_total"]:
        return None
    return 100.0 * c["slot_steps_active"] / c["slot_steps_total"]
