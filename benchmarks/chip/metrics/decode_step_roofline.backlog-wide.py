"""Segment executables, DeepSeek-V2 family: the least time of every
segment launched in the window over their measured device time (%).

With 1,023 pairs over 512 slots nearly every boundary admits prompts, so
nearly every segment is the fused refill executable; both executables are
read.  A segment's least time is its decode steps' (held weights and live
latent pages over peak bandwidth, or the operations over peak FLOP/s,
whichever is larger: ``harness/flops_mla_moe.py``, absorbed MLA, routed
tokens only in the expert layer; every slot is counted, live or not, as
the executable computes them all) plus, for a refill launch, the prefill
of its share of the prompts admitted in the window (real tokens only).
Context per slot: the mean real prompt plus half the decode budget."""
from harness import flops, flops_mla_moe, spec

PLAIN = "jit__paged_scan_decode"
REFILL = "jit__paged_refill_scan_decode"


def read(run):
    mods = (run.trace or {}).get("modules", {})
    plain, refill = mods.get(PLAIN, {}), mods.get(REFILL, {})
    launches = plain.get("count", 0) + refill.get("count", 0)
    if not launches:
        return None
    d = run.deploy
    c = run.counters
    prompt = c["real_tokens"] / c["emitted"] if c["emitted"] else d["prompt_len"]
    ctx = prompt + d["decode_budget"] / 2
    peak = spec.peaks(run.device_kind)
    step = flops_mla_moe.decode_step(run.model, int(d["slots"]), ctx)
    least = launches * int(run.cell.config["engine"]["segment_len"]) * \
        flops.least_time(step, peak)["seconds"]
    if refill.get("count") and c["emitted"]:
        per = flops_mla_moe.prefill(run.model, c["emitted"] / refill["count"],
                                    prompt)
        least += refill["count"] * flops.least_time(per, peak)["seconds"]
    seconds = plain.get("seconds", 0.0) + refill.get("seconds", 0.0)
    return 100.0 * least / seconds
