"""Slot runtime: 95th percentile of the host time from a segment's
completion to the next dispatch (`scope.boundary` spans, ms)."""
from harness import program_trace


def read(run):
    return program_trace.span_ms_p95(run.trace, "scope.boundary")
