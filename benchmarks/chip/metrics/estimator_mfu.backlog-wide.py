"""Model step, DeepSeek-V2 family: useful estimator FLOPs in the window
over the window times the chip's peak (%).  Useful FLOPs are 2 x the
weights a token multiplies by (``flops_mla_moe.active_params``: the held
experts at the share of a token's choices they receive) x (real prompt
tokens admitted + decode tokens of live slots); pad rows, pad tokens and
dead slots do not count."""
from harness import flops_mla_moe, spec


def read(run):
    if run.trace is None:
        return None
    c = run.counters
    tokens = c["real_tokens"] + c["slot_steps_active"]
    useful = 2.0 * flops_mla_moe.active_params(run.model) * tokens
    peak = spec.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * useful / (run.trace["window_s"] * peak)
