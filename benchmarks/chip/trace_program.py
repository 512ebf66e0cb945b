#!/usr/bin/env python3
"""Run one cell traced, as ``run_cell.py --trace 1`` does, and read the
program's own spans and named scopes from the trace as well.

    python3 benchmarks/chip/trace_program.py --workload qwen3-4b.backlog \\
        --seed 7 --seconds 50

``run_cell.py`` reduces a trace with ``harness/trace_reduce.py``, which
keeps the harness's ``bench.*`` spans only.  This runs the same set-up,
window and check, reduces with ``harness/program_trace.py`` (the same
numbers, plus ``spans``, ``host_bound_idle_s`` and ``scope_s``, and idle
gaps named by ``scope.*`` spans too) and adds the per-layer metrics of
``program_metrics.json`` that list the cell to the cell's own.  Its last
stdout line is ``run_cell.py``'s result line with those metrics in it.
Standard error gets, per executable, its device self-time split by named
scope and its largest unscoped ops, statistics of each span, and the
device-idle time under any named span.  It takes ``run_cell.py``'s
arguments and always traces one seed: ``--trace``, ``--seeds`` and
``--control`` are not used.
"""
from __future__ import annotations

import json
import shutil
import sys

import numpy as np

import run_cell
from run_cell import BENCH_DIR

PROGRAM_METRICS = BENCH_DIR / "program_metrics.json"


def program_metrics(workload: str):
    from harness import spec
    return [m for m in spec.load_json(PROGRAM_METRICS)["per_layer"]
            if workload in m["workloads"]]


def named_idle_s(trace) -> float:
    """Device-idle seconds in the window under any named host span, the
    harness's (``bench.*``) or the program's (``scope.*``)."""
    from harness import program_trace as pt
    from harness import trace_reduce as tr
    t0, t1 = tr.window(trace)
    _, busy_iv = tr.busy(trace, t0, t1)
    spans = [s for s in tr.host_spans(trace) if s[0] != tr.WINDOW_SPAN]
    spans += tr.host_spans(trace, pt.SPAN_PREFIX)
    covered = tr.union(tr.clip([(n, a, b - a) for n, a, b in spans], t0, t1))
    return pt.overlap(pt.idle(busy_iv, t0, t1), covered) / 1e9


def unscoped(trace, n: int = 6):
    """Per executable, the n ops in no named scope that took most device
    time (s)."""
    from harness import program_trace as pt
    from harness import trace_reduce as tr
    acc = {}
    for module, key, op, own in pt.op_scopes(trace, *tr.window(trace)):
        if key == pt.OTHER:
            per = acc.setdefault(module, {})
            per[op] = per.get(op, 0.0) + own / 1e9
    return {m: sorted(per.items(), key=lambda kv: -kv[1])[:n]
            for m, per in acc.items()}


def scope_shares(red):
    """Per executable, each scope path's share of its device self-time
    (%), largest first."""
    out = {}
    for module, per in red["scope_s"].items():
        tot = sum(per.values())
        if tot > 0:
            out[module] = {k: 100.0 * v / tot for k, v in
                           sorted(per.items(), key=lambda kv: -kv[1])}
    return out


def report(red, named_s: float, other) -> None:
    """The per-scope split of each executable, its largest unscoped ops,
    and what names the idle time, on standard error."""
    from harness.runner import say
    for module, shares in scope_shares(red).items():
        secs = sum(red["scope_s"][module].values())
        say(f"scope_s {module}: {secs:.4f} s "
            + json.dumps({k: round(v, 2) for k, v in shares.items()}))
        if secs > 1.0 and other.get(module):
            say(f"  unscoped: {json.dumps(other[module])}")
    for name, secs in sorted(red["spans"].items()):
        ms = np.asarray(secs) * 1e3
        say(f"span {name}: n {len(ms)}, p50 {np.percentile(ms, 50):.3f} "
            f"p95 {np.percentile(ms, 95):.3f} max {ms.max():.3f} "
            f"total {ms.sum():.1f} ms")
    n = sum(len(v) for v in red["spans"].values())
    idle_s = red["window_s"] - red["busy_s"]
    say(f"scope spans: {n} in {red['window_s']:.3f} s "
        f"({n / red['window_s']:.1f}/s); device idle {idle_s:.4f} s, of "
        f"it {red['host_bound_idle_s']:.4f} s under scope.* spans and "
        f"{named_s:.4f} s under any named span")


def main(argv=None) -> int:
    args = run_cell.parse_args(argv)
    from harness import program_trace, spec
    from harness.runner import Session, say
    cell = spec.load_cell(args.workload)
    cell.per_layer = cell.per_layer + program_metrics(cell.name)
    if args.rate is not None:
        cell.params = dict(cell.params, rate_qps=args.rate)
    import jax
    device = run_cell.device_info(jax)
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        say(f"no accelerator for {cell.name}: found {device['count']} "
            f"{device['platform']} device(s)")
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the scope table is read from each executable's own op metadata, which
    # the cache key leaves out by default: a build cached from source with
    # other scope names would load here and name nothing
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    out_dir = BENCH_DIR / "out"
    sess = Session(cell, run_cell.T_START, out_dir=out_dir)
    sess.setup(args.seed)
    run = sess.window(args.seed, args.seconds, True)
    if run.compiles_in_window:
        say(f"FAILED: {run.compiles_in_window} compilation(s) inside the "
            "measured window")
        return 3
    traced = out_dir / f"trace-{cell.name}-{args.seed}"
    pbs = sorted(traced.glob("**/*.xplane.pb"))
    if not pbs:
        say("FAILED: the traced run wrote no trace")
        return 4
    trace = program_trace.flatten(str(pbs[-1]))
    shutil.rmtree(traced, ignore_errors=True)
    red = program_trace.reduce(trace)
    report(red, named_idle_s(trace), unscoped(trace))
    del trace
    run.trace = red
    device = dict(device, memory_peak_bytes=run.memory_peak_bytes,
                  busy_s=red["busy_s"], window_s=red["window_s"])
    breakdown = {"device_ops": red["device_ops"],
                 "idle_gaps": red["idle_gaps"]}
    metrics = run_cell.read_metrics(run, cell.per_layer, spec)
    sess.release()
    check = sess.check(args.seed)
    print(json.dumps(run_cell.result_line(run, metrics, check, device,
                                          breakdown)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
