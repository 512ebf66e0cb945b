"""Sampler: device self-time under the `prefill` named scope per launch
of the fused refill + segment executable (ms): the prefill half of
`refill_segment_ms`."""
from harness import program_trace

EXECUTABLE = "jit__paged_refill_scan_decode"


def read(run):
    return program_trace.scope_ms(run.trace, EXECUTABLE, "prefill")
