#!/usr/bin/env python3
"""Run a cell of an expert-layer configuration as ``run_cell.py`` does, and
report how often the program's and the reference's top-k expert sets
differ at the positions the check compares.

    python3 benchmarks/chip/routing_agreement.py \\
        --workload deepseek-v2-lite.backlog-wide --seeds 7,8,9 \\
        --seconds 50 --control fp8

It takes ``run_cell.py``'s arguments and runs its set-up, window and check
for each seed; the check's reference readout is captured, and at its
tokens and positions every expert layer's router is evaluated twice: on
the program's own forward in the configuration's dtype (the program's
layers, one full-sequence pass over prompt and served tokens) and on the
float32 reference forward.  Near-tied router logits can send a token to
another expert between the two, which moves the logit gap the check
reads.  Standard error gets ``run_cell.py``'s per-seed summary with, under
``routing``: positions x expert layers compared, the share whose top-k
sets differ, the share whose held experts differ (only those change this
chip's result), and the logit gap at positions with and without such a
difference.  The last stdout line is the last seed's result line.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

import run_cell
from run_cell import BENCH_DIR


def _capture(ref):
    """Wrap ``ref.readout`` to keep the inputs of its plain call on the
    served tokens, and the logit gap at each position; returns what it
    keeps and a function that unwraps it."""
    got = {}
    real = ref.readout

    def readout(params, m, tokens, rows, cols, targets, extra=(), **kw):
        out = real(params, m, tokens, rows, cols, targets, extra, **kw)
        if kw.get("control", "none") == "none" and "tokens" not in got:
            got.update(tokens=np.asarray(tokens), rows=np.asarray(rows),
                       cols=np.asarray(cols),
                       gaps=out["max"] - out["at_target"])
        return out

    ref.readout = readout

    def restore():
        ref.readout = real

    return got, restore


def _program_routes(sess, tokens):
    """Top-k expert ids (layers, b, s, k) of each expert layer on the
    program's own full-sequence forward."""
    import jax
    import jax.numpy as jnp
    from repro.models import attention as attn_mod
    from repro.models import model as M
    from repro.models import moe as moe_mod
    from repro.models.common import rmsnorm
    cfg = sess.cfg
    b, s = tokens.shape
    cos, sin = M._cos_sin_full(cfg, {}, b, s)

    @jax.jit
    def layer(p, h):
        x = rmsnorm(p["attn_norm"], h, cfg.rmsnorm_eps)
        y, _ = attn_mod.mla_full(p["attn"], cfg, x, cos, sin)
        h = h + y
        x2 = rmsnorm(p["mlp_norm"], h, cfg.rmsnorm_eps)
        if "moe" not in p:
            from repro.models.mlp import mlp_forward
            return h + mlp_forward(p["mlp"], cfg, x2), None
        _, _, top = moe_mod.route(p["moe"], cfg, x2,
                                  precision=jax.lax.Precision.HIGHEST)
        y2, _ = moe_mod.moe_dropless(p["moe"], cfg, x2)
        return h + y2, top

    params = sess.params
    h = params["embed"][jnp.asarray(tokens)]
    routes = []
    for seg in params["segments"]:
        n = jax.tree.leaves(seg)[0].shape[0]
        for i in range(n):
            h, top = layer(jax.tree.map(lambda a, i=i: a[i], seg["0"]), h)
            if top is not None:
                routes.append(np.asarray(top))
    return np.stack(routes)


def _reference_routes(sess, tokens):
    """The same from the float32 reference (``references/mla_moe.py``)."""
    import jax
    import jax.numpy as jnp
    ref = sess.ref
    m = sess.cell.config["model"]
    eps = m["rmsnorm_eps"]

    @jax.jit
    def layer(p, h):
        h = h + ref._mla(p["attn"], m, "none",
                         ref._rmsnorm(h, p["attn_norm"]["scale"], eps))
        x = ref._rmsnorm(h, p["mlp_norm"]["scale"], eps)
        if "moe" not in p:
            return h + ref._swiglu(p["mlp"], "none", x), None
        probs = jax.nn.softmax(ref._mm(x, p["moe"]["router"], "none"), -1)
        _, top = jax.lax.top_k(probs, m["num_experts_per_tok"])
        return h + ref._moe(p["moe"], m, "none", x), top

    params = sess.params
    h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    routes = []
    for seg in params["segments"]:
        n = jax.tree.leaves(seg)[0].shape[0]
        for i in range(n):
            h, top = layer(jax.tree.map(lambda a, i=i: a[i], seg["0"]), h)
            if top is not None:
                routes.append(np.asarray(top))
    return np.stack(routes)


def agreement(sess, got) -> dict:
    tokens, rows, cols = got["tokens"], got["rows"], got["cols"]
    prog = _program_routes(sess, tokens)[:, rows, cols]     # (L, n, k)
    ref = _reference_routes(sess, tokens)[:, rows, cols]
    m = sess.cell.config["model"]
    lo = m["expert_offset"]
    hi = lo + (m["experts_held"] or m["num_experts"])
    ps, rs = np.sort(prog, -1), np.sort(ref, -1)
    differs = (ps != rs).any(-1)                            # (L, n)

    def held(x):
        return np.sort(np.where((x >= lo) & (x < hi), x, -1), -1)

    held_differs = (held(prog) != held(ref)).any(-1)
    at = held_differs.any(0)                                # per position
    out = {"positions": int(differs.shape[1]),
           "expert_layers": int(differs.shape[0]),
           "set_differs_share": float(differs.mean()),
           "held_differs_share": float(held_differs.mean()),
           "positions_with_a_held_difference": float(at.mean())}
    gaps = got["gaps"]
    out["logit_gap_max_with"] = float(gaps[at].max()) if at.any() else None
    out["logit_gap_max_without"] = float(gaps[~at].max()) \
        if (~at).any() else None
    return out


def main(argv=None) -> int:
    args = run_cell.parse_args(argv)
    from harness import spec
    from harness.runner import Session, say
    cell = spec.load_cell(args.workload)
    import jax
    device = run_cell.device_info(jax)
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        say(f"no accelerator for {cell.name}: found {device['count']} "
            f"{device['platform']} device(s)")
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out_dir = BENCH_DIR / "out"
    sess = Session(cell, run_cell.T_START, out_dir=out_dir)
    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
             else [args.seed])
    line = None
    for seed in seeds:
        got, restore = _capture(sess.ref)
        traced_dir = out_dir / f"trace-{cell.name}-{seed}"
        try:
            res, rc = run_cell.one_seed(sess, seed, args, device, traced_dir)
        finally:
            restore()
        if rc:
            return rc
        run, metrics, check, dev, breakdown = res
        line = run_cell.result_line(run, metrics, check, dev, breakdown)
        summary = {"seed": seed, "setup_s": run.setup_s,
                   "correct": line["correct"], "checks": line["checks"],
                   "metrics": metrics, "decided": run.decided,
                   "rate_s": run.rate_s, "attempted": run.attempted,
                   "memory_peak_bytes": run.memory_peak_bytes}
        if "control" in check:
            summary["control"] = {
                k: v["value"] for k, v in check["control"]["checks"].items()}
        summary["routing"] = agreement(sess, got)
        print(json.dumps(summary), file=sys.stderr, flush=True)
        sess.t_start = time.perf_counter()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
