"""Model step: useful estimator FLOPs in the window over the window
times the chip's peak (%).  Useful FLOPs are 2 x the matrix-product
weights x (real prompt tokens admitted + decode tokens of live slots);
pad rows, pad tokens and dead slots do not count."""
from harness import flops, spec


def read(run):
    if run.trace is None:
        return None
    c = run.counters
    tokens = c["real_tokens"] + c["slot_steps_active"]
    useful = 2.0 * flops.matmul_params(run.model) * tokens
    peak = spec.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * useful / (run.trace["window_s"] * peak)
