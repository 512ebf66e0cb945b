"""Decode executable: device self-time under the `attn` named scope in
the plain decode segments, per decode step (ms): attention's share of one
step."""
from harness import program_trace

EXECUTABLE = "jit__paged_scan_decode"


def read(run):
    steps = int(run.cell.config["engine"]["segment_len"])
    return program_trace.scope_ms(run.trace, EXECUTABLE, "attn", steps)
