"""End-to-end serve-path latency: p50/p95 per-query latency and host
transfer, legacy per-token decode loop vs the fused scan pipeline.

Three sections:

  decode  — ``sampler.generate`` (one jitted scan, YES/NO logit pair to
            host) against the pre-fusion reference loop (one jitted
            dispatch per token, full (b, T, V) float32 logits to host)
  predict — ``ScopeEngine.predict`` per query, cold cache (estimator runs)
            and warm cache (pure assembly)
  route   — predict + ``FixedAlphaPolicy`` decide per query

Rows go to stdout CSV (via ``benchmarks.run``) and to
``benchmarks/BENCH_serve_latency.json`` — the start of the BENCH_*.json
trajectory.  Standalone:

  PYTHONPATH=src python benchmarks/bench_serve_latency.py --smoke
"""
from __future__ import annotations

import argparse
import functools
import os
import time
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BENCH_PATH = os.path.join(os.path.dirname(__file__),
                          "BENCH_serve_latency.json")


# ---------------------------------------------------------------------------
# Legacy decode loop (pre-fusion reference, pinned here for the comparison)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(3,))
def _legacy_decode_step(params, cfg, token, caches, pos):
    from repro.models import model as M
    logits, caches = M.decode_step(params, cfg, token, caches, pos)
    return logits[:, 0], caches


def legacy_generate(params, cfg, prompts, *, max_new_tokens=12,
                    temperature=0.0, rng=None, stop_at_eos=True):
    """One jitted dispatch per token; full (b, T, V) logits copied to host."""
    from repro.data.tokenizer import EOS, PAD
    from repro.models import model as M
    from repro.serving.sampler import _pad_caches
    prompts = jnp.asarray(prompts, jnp.int32)
    b, lp = prompts.shape
    last, caches = M.prefill(params, cfg, {"tokens": prompts})
    caches = _pad_caches(caches, lp + max_new_tokens, lp)
    last = last.astype(jnp.float32)
    outs, step_logits = [], []
    done = jnp.zeros((b,), bool)
    key = rng if rng is not None else jax.random.PRNGKey(0)
    for t in range(max_new_tokens):
        if temperature > 0.0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, last / temperature, axis=-1)
        else:
            nxt = jnp.argmax(last, axis=-1)
        nxt = jnp.where(done, PAD, nxt).astype(jnp.int32)
        outs.append(nxt)
        step_logits.append(last)
        if stop_at_eos:
            done = done | (nxt == EOS)
        last, caches = _legacy_decode_step(params, cfg, nxt[:, None], caches,
                                           lp + t)
        last = last.astype(jnp.float32)
    gen = np.asarray(jnp.stack(outs, axis=1))
    lg = np.asarray(jnp.stack(step_logits, axis=1))
    return gen, lg


# ---------------------------------------------------------------------------
# Measurement helpers
# ---------------------------------------------------------------------------
def _percentiles(times_s: List[float]) -> Dict[str, float]:
    a = np.asarray(times_s, np.float64) * 1e6          # us
    return {"p50_us": float(np.percentile(a, 50)),
            "p95_us": float(np.percentile(a, 95)),
            "mean_us": float(a.mean())}


def _time_calls(fn: Callable[[], None], repeats: int, *,
                warmup: int = 2) -> Dict[str, float]:
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return _percentiles(times)


# ---------------------------------------------------------------------------
# Sections
# ---------------------------------------------------------------------------
def bench_decode(cfg, params, *, batch: int, prompt_len: int,
                 max_new_tokens: int, repeats: int) -> List[Dict]:
    from repro.serving import sampler
    rng = np.random.default_rng(0)
    prompts = rng.integers(3, min(100, cfg.vocab_size),
                           size=(batch, prompt_len)).astype(np.int32)
    bytes_legacy = batch * max_new_tokens * (cfg.vocab_size * 4 + 4)
    bytes_fused = batch * max_new_tokens * (2 * 4 + 4)

    t_old = _time_calls(
        lambda: legacy_generate(params, cfg, prompts,
                                max_new_tokens=max_new_tokens), repeats)
    t_new = _time_calls(
        lambda: sampler.generate(params, cfg, prompts,
                                 max_new_tokens=max_new_tokens), repeats)
    speedup = t_old["p50_us"] / max(t_new["p50_us"], 1e-9)
    per_q = 1.0 / batch
    return [
        {"name": "serve/decode_legacy_loop",
         **{k: v * per_q for k, v in t_old.items()},
         "detail": {"batch": batch, "new_tokens": max_new_tokens,
                    "host_bytes_per_batch": bytes_legacy}},
        {"name": "serve/decode_fused_scan",
         **{k: v * per_q for k, v in t_new.items()},
         "detail": {"batch": batch, "new_tokens": max_new_tokens,
                    "host_bytes_per_batch": bytes_fused,
                    "speedup_vs_legacy": round(speedup, 2),
                    "transfer_cut":
                        round(bytes_legacy / max(bytes_fused, 1), 1)}},
    ]


def bench_predict_route(engine, queries, *, alpha: float = 0.6) -> List[Dict]:
    """Per-query p50/p95 for predict (cold + warm) and route (warm)."""
    from repro.api import FixedAlphaPolicy, RouteRequest
    policy = FixedAlphaPolicy(alpha)
    # warm the jit caches on a throwaway prefix so cold rows measure the
    # serve path, not one-off XLA compilation
    for q in queries[:2]:
        engine.predict(RouteRequest([q]))
    engine.cache.clear()

    cold, warm, route = [], [], []
    for q in queries:
        t0 = time.perf_counter()
        engine.predict(RouteRequest([q]))
        cold.append(time.perf_counter() - t0)
    for q in queries:
        t0 = time.perf_counter()
        engine.predict(RouteRequest([q]))
        warm.append(time.perf_counter() - t0)
    for q in queries:
        t0 = time.perf_counter()
        engine.route(RouteRequest([q]), policy)
        route.append(time.perf_counter() - t0)

    t_cold, t_warm, t_route = (_percentiles(x) for x in (cold, warm, route))
    n_models = len(engine.registry.routable())
    return [
        {"name": "serve/predict_cold", **t_cold,
         "detail": {"models": n_models, "queries": len(queries)}},
        {"name": "serve/predict_warm", **t_warm,
         "detail": {"models": n_models,
                    "speedup_vs_cold":
                        round(t_cold["p50_us"] / max(t_warm["p50_us"], 1e-9),
                              1)}},
        {"name": "serve/route_warm", **t_route,
         "detail": {"policy": "fixed_alpha", "alpha": alpha}},
    ]


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------
def _emit(rows: List[Dict], *, smoke: bool) -> None:
    from benchmarks._io import write_bench_json
    write_bench_json(BENCH_PATH, {
        "bench": "serve_latency", "smoke": smoke,
        "unix_time": int(time.time()), "rows": rows})


def _as_csv_rows(rows: List[Dict]) -> List[Tuple[str, float, str]]:
    out = []
    for r in rows:
        detail = ";".join(f"{k}={v}" for k, v in r["detail"].items())
        out.append((r["name"], r["p50_us"],
                    f"p95_us={r['p95_us']:.1f};{detail}"))
    return out


def run(bundle) -> List[Tuple[str, float, str]]:
    """benchmarks.run entry point: full trained-estimator measurement."""
    rows = bench_decode(bundle.cfg, bundle.params, batch=32, prompt_len=49,
                        max_new_tokens=12, repeats=20)
    engine = bundle.engine(bundle.seen)
    queries = [bundle.data.queries[int(q)]
               for q in bundle.data.test_qids[:32]]
    rows += bench_predict_route(engine, queries)
    _emit(rows, smoke=False)
    return _as_csv_rows(rows)


def _smoke_setup():
    """Tiny untrained world — latency only, no training, CI-sized."""
    from repro.api import EngineConfig, ScopeEngine
    from repro.configs.scope_estimator import TINY
    from repro.core.estimator import ReasoningEstimator
    from repro.core.fingerprint import FingerprintLibrary, build_anchor_set
    from repro.core.retrieval import AnchorRetriever
    from repro.data.datasets import build_scope_data, stratified_anchors
    from repro.data.worldsim import World
    from repro.models import model as M

    world = World(seed=0)
    data = build_scope_data(world, n_queries=240, seed=0)
    aset = build_anchor_set(world, stratified_anchors(world, n=60, seed=7))
    library = FingerprintLibrary(aset)
    for m in data.models:
        library.onboard(world, m, seed=3)
    params = M.init_params(jax.random.PRNGKey(0), TINY)
    engine = ScopeEngine.build(EngineConfig(
        estimator=ReasoningEstimator(TINY, params),
        retriever=AnchorRetriever(aset), library=library,
        models_meta={m: world.models[m] for m in data.models}))
    queries = [data.queries[int(q)] for q in data.test_qids[:12]]
    return TINY, params, engine, queries


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny untrained setup (CI gate), no bundle training")
    ap.add_argument("--repeats", type=int, default=None)
    args = ap.parse_args(argv)

    if args.smoke:
        cfg, params, engine, queries = _smoke_setup()
        repeats = args.repeats or 5
        rows = bench_decode(cfg, params, batch=8, prompt_len=49,
                            max_new_tokens=12, repeats=repeats)
        rows += bench_predict_route(engine, queries)
        _emit(rows, smoke=True)
    else:
        from benchmarks.common import get_bundle
        rows_csv = run(get_bundle())
        for name, us, derived in rows_csv:
            print(f"{name},{us:.2f},{derived}")
        return 0
    print("name,us_per_call,derived")
    for name, us, derived in _as_csv_rows(rows):
        print(f"{name},{us:.2f},{derived}")
    return 0


if __name__ == "__main__":
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    raise SystemExit(main())
