#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 benchmarks/chip/run_cell.py --workload qwen3-4b.poisson \\
        --seed 7 --seconds 30 --trace 0

The cell, its configuration, traffic mix and metrics are read from
``BENCHMARK.json`` and the files beside this script (``harness/spec.py``).
Set-up (weights from the seed, world, engine, warm-up of every shape the
cell uses, any cache fill its traffic needs) counts as ``setup_s``; then
the window offers the cell's traffic for ``--seconds`` and nothing may
compile inside it.  Once it has closed, a sample of what it served is
compared with the plain references (``harness/checks.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last the ``checks``, each number beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.  Options for measuring the benchmark itself, which its
own runs do not use: ``--rate`` overrides the cell's arrival rate (the
capacity sweep), ``--seeds a,b,..`` runs several seeds in one process and
``--control fp8`` adds the lower-precision control's readings to each.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--seeds", default=None)
    ap.add_argument("--control", default="none", choices=("none", "fp8"))
    return ap.parse_args(argv)


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def result_line(run, metrics, check, device, breakdown=None) -> dict:
    out = {"correct": bool(check["correct"]) and run.failed == 0,
           "attempted": int(run.attempted), "failed": int(run.failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: [v["value"], v["limit"]]
                     for k, v in check["checks"].items()}
    return out


def read_metrics(run, names_units, spec_mod) -> dict:
    out = {}
    for m in names_units:
        v = spec_mod.metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def halves(ttd_ms):
    import numpy as np
    n = len(ttd_ms) // 2
    if n == 0:
        return []
    return [float(np.median(ttd_ms[:n])), float(np.median(ttd_ms[n:]))]


def one_seed(sess, seed, args, device, traced_dir):
    from harness import spec, trace_reduce
    from harness.runner import say
    sess.setup(seed)
    run = sess.window(seed, args.seconds, bool(args.trace))
    if run.compiles_in_window:
        say(f"FAILED: {run.compiles_in_window} compilation(s) inside the "
            "measured window")
        return None, 3
    device = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    breakdown = None
    if args.trace:
        pbs = sorted(traced_dir.glob("**/*.xplane.pb"))
        if not pbs:
            say("FAILED: the traced run wrote no trace")
            return None, 4
        run.trace = trace_reduce.reduce(trace_reduce.flatten(str(pbs[-1])))
        shutil.rmtree(traced_dir, ignore_errors=True)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}
        metrics = read_metrics(run, sess.cell.per_layer, spec)
    else:
        metrics = read_metrics(run, sess.cell.end_to_end, spec)
    sess.release()
    check = sess.check(seed)
    if args.control != "none":
        ctl = sess.check(seed, control=args.control)
        check["control"] = ctl
    return (run, metrics, check, device, breakdown), 0


def execute(cell, args, device, t_start) -> int:
    """Everything after the look for the chip: set-up, window, check and
    the result line, for each seed asked for."""
    from harness.runner import Session, say
    out_dir = BENCH_DIR / "out"
    sess = Session(cell, t_start, out_dir=out_dir)
    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
             else [args.seed])
    last = None
    for seed in seeds:
        traced_dir = out_dir / f"trace-{cell.name}-{seed}"
        got, rc = one_seed(sess, seed, args, device, traced_dir)
        if rc:
            return rc
        run, metrics, check, dev, breakdown = got
        line = result_line(run, metrics, check, dev, breakdown)
        if args.seeds:
            summary = {"seed": seed, "setup_s": run.setup_s,
                       "correct": line["correct"], "checks": line["checks"],
                       "metrics": metrics,
                       "decided": run.decided, "rate_s": run.rate_s,
                       "attempted": run.attempted,
                       # a backlog that grows shows as a later half slower
                       "ttd_p50_halves": halves(run.ttd_ms)}
            if "control" in check:
                summary["control"] = {
                    k: v["value"] for k, v in check["control"]["checks"].items()}
            print(json.dumps(summary), file=sys.stderr, flush=True)
        sess.t_start = time.perf_counter()
        last = (line, check)
    line, check = last
    for name, c in check["checks"].items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    args = parse_args(argv)
    from harness import spec
    cell = spec.load_cell(args.workload)
    if args.rate is not None:
        cell.params = dict(cell.params, rate_qps=args.rate)

    import jax
    device = device_info(jax)
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(f"no accelerator for {cell.name}: found {device['count']} "
              f"{device['platform']} device(s), the cell needs {cell.chips} "
              "TPU chip(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"cell {cell.name}: {device}, compile cache {cache}",
          file=sys.stderr, flush=True)
    return execute(cell, args, device, T_START)


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
