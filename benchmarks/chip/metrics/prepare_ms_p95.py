"""Engine intake: 95th percentile of one non-empty request's retrieval,
cache probe and prompt serialization (`scope.prepare` spans, ms)."""
from harness import program_trace


def read(run):
    return program_trace.span_ms_p95(run.trace, "scope.prepare")
