"""Decode executable: the least time a segment of decode steps could take
on this chip over its measured device time per launch (%).  The least time
is the larger of the weights and live KV over peak bandwidth and the
operations over peak FLOP/s (``harness/flops.py``); every slot is counted,
live or not, as the executable computes them all.  Context per slot: the
mean real prompt plus half the decode budget."""
from harness import flops, spec

EXECUTABLE = "jit__paged_scan_decode"


def read(run):
    m = (run.trace or {}).get("modules", {}).get(EXECUTABLE)
    if not m or not m["count"]:
        return None
    d = run.deploy
    c = run.counters
    prompt = c["real_tokens"] / c["emitted"] if c["emitted"] else d["prompt_len"]
    ctx = prompt + d["decode_budget"] / 2
    step = flops.decode_step(run.model, int(d["slots"]), ctx)
    peak = spec.peaks(run.device_kind)
    least = int(run.cell.config["engine"]["segment_len"]) * \
        flops.least_time(step, peak)["seconds"]
    return 100.0 * least / (m["seconds"] / m["count"])
