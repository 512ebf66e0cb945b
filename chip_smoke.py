#!/usr/bin/env python3
"""Chip smoke: the SCOPE serve path at the published estimator width on TPU.

    python chip_smoke.py               # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4     # sharded stream vs one-chip stream

Drives ``ScopeEngine`` — retrieval, the microbatch scheduler, prefill and
the decode scan through the dense and the paged KV cache, the parse and a
routing policy — with the ``scope-qwen3-4b`` estimator (36 layers, d_model
2560, 32/8 heads x 128, vocab 151,936) in bfloat16.  The world, anchors,
fingerprints and weights are drawn from ``--seed``; no trained full-width
checkpoint exists, so the weights are random.  Nothing outside the
checkout is read.

One chip, one process:

  (a) device   — the first JAX device must be a TPU; there is no fallback
  (b) batch    — ``engine.serve`` over a few queries x the 7 pool models
  (c) stream   — ``engine.serve_stream(refill=True)`` on the paged KV
                 cache, once with the XLA and once with the Pallas paged
                 decode-attention kernel; a warm-up stream first, then the
                 measured stream must compile nothing new
  (d) parity   — a 2-layer cut of the same width against the float32
                 reference forward (``models/reference.py``) on the CPU
                 device: prefill logits and per-step decision logits of
                 the dense, paged-XLA and paged-Pallas decode paths
  (e) kernels  — every Pallas kernel, compiled, against its XLA twin at
                 the estimator's head shapes

Every answered pair must have status OK, the faults ledger must show no
retry, quarantine, degrade or unexpected failure, and every logit read
must be finite.  Times printed are of this smoke run, compilation
included — not benchmark numbers.  Details go to
``chiprun_out/chip_smoke_<chips>.json``.  The last line of standard
output is ``{"ok": true, "device": {...}}`` and appears only on success.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import (  # noqa: E402
    EngineConfig, FixedAlphaPolicy, RouteRequest, ScopeEngine)
from repro.api.cache import query_key  # noqa: E402
from repro.configs.scope_estimator import CONFIG  # noqa: E402
from repro.core.estimator import ReasoningEstimator  # noqa: E402
from repro.core.fingerprint import (  # noqa: E402
    FingerprintLibrary, build_anchor_set)
from repro.core.retrieval import AnchorRetriever  # noqa: E402
from repro.core.status import STATUS_OK  # noqa: E402
from repro.data.datasets import (  # noqa: E402
    build_scope_data, stratified_anchors)
from repro.data.worldsim import EMBED_DIM, World  # noqa: E402
from repro.kernels import decode_attention as da  # noqa: E402
from repro.kernels import flash_attention as fa  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels import ssd_scan as ss  # noqa: E402
from repro.kernels import topk_retrieval as tk  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serve_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import reference as R  # noqa: E402
from repro.serving import sampler  # noqa: E402
from repro.serving.kv_pool import KVPool  # noqa: E402
from repro.serving.scheduler import (  # noqa: E402
    BucketConfig, MicrobatchScheduler, decode_compile_counts)

BUDGET = 12                 # decode tokens per pair (estimator default)
SEGMENT = 4                 # decode steps per scan segment (refill path)
PAGE = 16                   # KV page size in token positions
BUCKET_BATCH = 8            # slot batch of the stream phases
PROMPT_LEN = 64             # serialization.MAX_PROMPT_LEN: one length bucket
# kernel vs XLA twin: RMS error within 1% of the twin's RMS, and no
# element off by more than 2^-6 of the largest output — two bf16 ulps
# there, as kernel and twin each round their bf16 output once.  (An
# element's error against the global RMS would flag one ulp of an early
# causal row, whose output is a single value row of v.)
KERNEL_TOL = {"rel_rms": 1e-2, "rel_peak": 2.0 ** -6}
TOPK_TOL = 1e-2             # cosine units
DECISION_FIELDS = ("y_hat", "well_formed", "len_hat", "pred_tokens")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"({phase}) {msg}", flush=True)


class Recorder(ReasoningEstimator):
    """The serve-path estimator, keeping what the checks read: the
    (tokens, decision logits) of every batch dispatch and every slot
    state the stream path opens."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.chunks = []
        self.runs = []

    def dispatch_batch(self, prompts, **kw):
        handle = super().dispatch_batch(prompts, **kw)
        self.chunks.extend(handle.chunks)
        return handle

    def open_slots(self, tokens, **kw):
        run = super().open_slots(tokens, **kw)
        self.runs.append(run)
        return run

    def logits_finite(self) -> bool:
        return (all(np.isfinite(np.asarray(d)).all() for _, d in self.chunks)
                and all(np.isfinite(np.asarray(r.state.last_logits)).all()
                        for r in self.runs))

    def forget(self) -> None:
        self.chunks, self.runs = [], []


def build_world(seed: int):
    world = World(seed=seed)
    data = build_scope_data(world, n_queries=200, seed=seed)
    aset = build_anchor_set(world, stratified_anchors(world, n=80,
                                                      seed=seed + 7))
    lib = FingerprintLibrary(aset)
    for m in data.models:
        lib.onboard(world, m, seed=seed + 3)
    return world, data, aset, lib


def cached_pairs(engine, data, qids):
    """Per-model cache columns of every (query, model) pair served."""
    keys = [query_key(data.queries[q]) for q in qids]
    cols = {}
    for m in engine.registry.routable():
        col = engine.cache.get_many(keys, m, engine.config.estimator_version)
        check(bool(col.mask.all()), f"pair of {m} missing from the cache")
        cols[m] = col
    return cols


def check_pairs_ok(cols, phase: str) -> int:
    n = 0
    for m, col in cols.items():
        check(bool((col.status == STATUS_OK).all()),
              f"{phase}: {m} has pairs not answered OK: {col.status}")
        n += len(col.status)
    return n


def check_faults(sched, phase: str) -> dict:
    f = sched.stats.as_dict()["faults"]
    for k in ("retries", "quarantined", "degraded", "failed", "unexpected",
              "injected"):
        check(f[k] == 0, f"{phase}: faults ledger {k}={f[k]} ({f})")
    return f


def decisions_agree(cols_a, cols_b) -> float:
    """Share of (query, model) pairs two runs parsed identically."""
    same = [np.all([getattr(cols_a[m], f) == getattr(cols_b[m], f)
                    for f in DECISION_FIELDS], axis=0) for m in cols_a]
    return float(np.concatenate(same).mean())


def check_close(err, tol, what: str) -> None:
    check(err["finite"] and all(err[k] <= v for k, v in tol.items()),
          f"{what}: {err} exceeds {tol}")


def kv_pool_for(b: int, width: int):
    return KVPool(n_pages=b * math.ceil((width + BUDGET) / PAGE),
                  page_size=PAGE)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def phase_batch(est, retriever, lib, world, data, qids, out):
    engine = ScopeEngine.build(EngineConfig(
        estimator=est, retriever=retriever, library=lib,
        models_meta={m: world.models[m] for m in data.models}))
    t0 = time.perf_counter()
    report = engine.serve(data, qids, FixedAlphaPolicy(0.6))
    secs = time.perf_counter() - t0
    n = check_pairs_ok(cached_pairs(engine, data, qids), "b")
    check(all(d.status == "OK" for d in report.decisions),
          "b: a routed decision is not OK")
    check(est.logits_finite(), "b: non-finite decision logits")
    out["batch"] = {"pairs": n, "queries": len(qids),
                    "overhead_tokens": report.overhead_tokens,
                    "smoke_seconds_incl_compile": secs}
    say("b", f"batch serve: {n} pairs OK over {len(qids)} queries x "
             f"{len(data.models)} models, {report.overhead_tokens} decode "
             f"tokens; smoke time {secs:.1f} s incl. compile")
    est.forget()


def phase_stream(est, retriever, lib, world, data, warm_qids, qids, out):
    bucket = BucketConfig(batch_sizes=(BUCKET_BATCH,),
                          prompt_lens=(PROMPT_LEN,))
    policy = FixedAlphaPolicy(0.6)
    meta = {m: world.models[m] for m in data.models}

    def ticks(q):
        return [q[:2], q[2:]]

    cols = {}
    for kernel in ("xla", "pallas"):
        engine = ScopeEngine.build(EngineConfig(
            estimator=est, retriever=retriever, library=lib,
            models_meta=meta, refill=True, segment_len=SEGMENT,
            kv_page_size=PAGE, kv_kernel=kernel))
        warm = MicrobatchScheduler(bucket)
        t0 = time.perf_counter()
        list(engine.serve_stream(data, ticks(warm_qids), policy,
                                 scheduler=warm))
        warm_s = time.perf_counter() - t0
        check_faults(warm, f"c/{kernel} warm-up")
        before = decode_compile_counts()
        sched = MicrobatchScheduler(bucket)
        t0 = time.perf_counter()
        reports = list(engine.serve_stream(data, ticks(qids), policy,
                                           scheduler=sched))
        secs = time.perf_counter() - t0
        after = decode_compile_counts()
        new = {k: after[k] - before[k] for k in after
               if after[k] != before[k]}
        check(not new, f"c/{kernel}: compiled after warm-up: {new}")
        faults = check_faults(sched, f"c/{kernel}")
        check(all(d.status == "OK" for r in reports for d in r.decisions),
              f"c/{kernel}: a routed decision is not OK")
        cols[kernel] = cached_pairs(engine, data, qids)
        n = check_pairs_ok(cols[kernel], f"c/{kernel}")
        check(est.logits_finite(), f"c/{kernel}: non-finite logits")
        st = sched.stats
        out[f"stream_{kernel}"] = {
            "pairs": n, "compiles_after_warmup": 0, "faults": faults,
            "slots_refilled": st.slots_refilled,
            "slot_occupancy": st.slot_occupancy,
            "kv_pages_peak": st.pages_peak,
            "smoke_seconds_warmup_incl_compile": warm_s,
            "smoke_seconds_measured": secs}
        say("c", f"stream kv_kernel={kernel}: {n} pairs OK, 0 compiles "
                 f"after warm-up, faults all 0, {st.slots_refilled} slots "
                 f"refilled, occupancy {st.slot_occupancy:.3f}; smoke time "
                 f"warm-up {warm_s:.1f} s incl. compile, measured "
                 f"{secs:.2f} s")
        est.forget()
    # greedy decodes of random weights can part at a near-tied argmax, so
    # the kernels' numbers are held to each other in (d); here, report
    agree = decisions_agree(cols["pallas"], cols["xla"])
    out["stream_pallas_vs_xla"] = {"decisions_agree": agree}
    say("c", f"pallas vs xla stream: {agree:.3f} of pairs parsed "
             "identically")


def phase_parity(seed, out):
    cfg = dataclasses.replace(CONFIG, num_layers=2)
    params = M.init_params(jax.random.PRNGKey(seed + 1), cfg)
    rng = np.random.default_rng(seed)
    b, L = BUCKET_BATCH, PROMPT_LEN
    prompts = rng.integers(0, cfg.vocab_size, size=(b, L)).astype(np.int32)
    lens = rng.integers(L // 2, L + 1, size=b)
    lens[0] = L
    try:
        ref_dev = jax.devices("cpu")[0]
    except RuntimeError:
        ref_dev = None          # no CPU backend: f32 "highest" on the chip
    where = "cpu" if ref_dev is not None else "tpu (f32, highest)"
    paths = {"dense": {},
             "paged-xla": {"kv_pool": kv_pool_for(b, L),
                           "kv_kernel": da.KernelType.XLA},
             "paged-pallas": {"kv_pool": kv_pool_for(b, L),
                              "kv_kernel": da.KernelType.PALLAS}}
    res, decodes = {}, {}
    for name, kw in paths.items():
        st = sampler.prefill_state(params, cfg, prompts,
                                   max_new_tokens=BUDGET, prompt_lens=lens,
                                   **kw)
        last = np.asarray(st.last_logits)
        _, gen, dec = sampler.decode_segment(params, cfg, st, BUDGET)
        decodes[name] = (np.asarray(gen), np.asarray(dec))
        r = R.serve_parity(params, cfg, prompts, lens, last, gen, dec,
                           sampler.DECISION_TOKENS, device=ref_dev)
        bad = R.parity_failures(r, cfg.dtype)
        res[name] = r
        say("d", f"parity {name} vs f32 reference on {where}: prefill "
                 f"rel_rms {r['prefill']['rel_rms']:.4f} rel_max "
                 f"{r['prefill']['rel_max']:.4f}; decision rel_rms "
                 f"{r['decision']['rel_rms']:.4f} rel_max "
                 f"{r['decision']['rel_max']:.4f} (tol "
                 f"{R.TOLERANCE[cfg.dtype]})")
        check(not bad, f"d: {name} parity: {bad}")
    # the Pallas paged kernel against the XLA paged path, same model
    kv = R.compare_decodes(decodes["paged-pallas"], decodes["paged-xla"])
    say("d", f"paged-pallas vs paged-xla decision logits: rel_rms "
             f"{kv['rel_rms']:.2e} rel_max {kv['rel_max']:.2e} over "
             f"{kv['steps_compared']:.3f} of steps; {kv['rows_equal']:.3f} "
             "of rows generated identical tokens")
    check_close(kv, R.TOLERANCE[cfg.dtype], "d: paged-pallas vs paged-xla")
    res["paged-pallas_vs_paged-xla"] = kv
    out["parity_2_layer"] = {"reference_device": where, "rows": b,
                             "steps": BUDGET, "tolerance":
                             R.TOLERANCE[cfg.dtype], "paths": res}


def phase_kernels(seed, out):
    hq, hkv = CONFIG.num_heads, CONFIG.num_kv_heads
    d = CONFIG.resolved_head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 16)
    bf = jnp.bfloat16
    b, S = BUCKET_BATCH, 512
    n_w = math.ceil((PROMPT_LEN + BUDGET) / PAGE)
    kv_cap = PROMPT_LEN + BUDGET

    def normal(i, shape, dt=bf):
        return jax.random.normal(ks[i], shape, jnp.float32).astype(dt)

    # flash attention over one prompt block
    fq, fk, fv = (normal(0, (2, hq, 128, d)), normal(1, (2, hkv, 128, d)),
                  normal(2, (2, hkv, 128, d)))
    # dense decode attention over a 512-slot cache, ragged lengths
    dq = normal(3, (b, hq, 1, d))
    dk, dv = normal(4, (b, hkv, S, d)), normal(5, (b, hkv, S, d))
    dlen = jax.random.randint(ks[6], (b,), 1, S + 1)
    # paged decode attention: token-major pages, shuffled page table,
    # trash page last
    n_pages = b * n_w + 1
    table = jax.random.permutation(ks[7], b * n_w).reshape(b, n_w)
    pk = normal(8, (n_pages, PAGE, hkv, d))
    pv = normal(9, (n_pages, PAGE, hkv, d))
    plen = jax.random.randint(ks[10], (b,), 1, kv_cap + 1)
    # SSD: Mamba2 heads (p 64, state 128), two 128-token chunks
    sx = normal(11, (1, 256, 8, 64), jnp.float32)
    sdt = jax.nn.softplus(normal(12, (1, 256, 8), jnp.float32))
    sA = -jnp.exp(normal(13, (8,), jnp.float32))
    sB = normal(14, (1, 256, 128), jnp.float32)
    sC = normal(15, (1, 256, 128), jnp.float32)
    # top-k retrieval at the world's query-embedding width
    tq = np.asarray(jax.random.normal(ks[0], (64, EMBED_DIM)), np.float32)
    ta = np.asarray(jax.random.normal(ks[1], (512, EMBED_DIM)), np.float32)

    # (kernel, XLA twin, arguments): arrays go in as arguments, so the
    # twin runs on the chip rather than being constant-folded on the host
    paged_kw = {"page_size": PAGE, "kv_cap": kv_cap}
    cases = {
        "flash_attention": (
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
            lambda q, k, v: ref.attention(q, k, v, causal=True),
            (fq, fk, fv)),
        "decode_attention": (
            da.decode_attention,
            lambda *a: ops.decode_attention(*a, impl="xla"),
            (dq, dk, dv, dlen)),
        "paged_decode_attention": (
            lambda *a: da.paged_decode_attention(*a, **paged_kw),
            lambda *a: ops.paged_decode_attention(
                *a, kernel=da.KernelType.XLA, **paged_kw),
            (dq, pk, pv, plen, table)),
        "ssd_scan": (
            lambda *a: ss.ssd_scan(*a, chunk=128),
            lambda *a: ref.ssd(*a, chunk=128),
            (sx, sdt, sA, sB, sC)),
    }
    res = {}
    for name, (kernel, twin, args) in cases.items():
        hlo = jax.jit(kernel).lower(*args).as_text()
        check("tpu_custom_call" in hlo, f"e: {name} did not compile to a "
                                        "Mosaic kernel")
        got = jax.tree.leaves(jax.jit(kernel)(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(jax.jit(twin)(*args))
        errs = []
        for g, w in zip(got, want, strict=True):
            e = R.rel_errors(g, w)
            e["rel_peak"] = float(np.abs(np.asarray(g, np.float64)
                                         - np.asarray(w, np.float64)).max()
                                  / np.abs(np.asarray(w, np.float64)).max())
            errs.append(e)
        r = {k: max(e[k] for e in errs)
             for k in ("rel_rms", "rel_max", "rel_peak")}
        r["finite"] = all(e["finite"] for e in errs)
        res[name] = r
        say("e", f"{name}: compiled, vs XLA twin rel_rms "
                 f"{r['rel_rms']:.2e} rel_peak {r['rel_peak']:.2e} "
                 f"(rel_max {r['rel_max']:.2e})")
        check_close(r, KERNEL_TOL, f"e: {name} vs XLA twin")

    # top-k: the kernel's picks must be a true top-k of the exact sims
    k = 5
    hlo = jax.jit(lambda q, a: tk.topk_retrieval(q, a, k)).lower(
        tq, ta).as_text()
    check("tpu_custom_call" in hlo, "e: topk_retrieval did not compile")
    sc, ix = (np.asarray(x) for x in tk.topk_retrieval(tq, ta, k))
    qn = tq / np.linalg.norm(tq, axis=1, keepdims=True)
    an = ta / np.linalg.norm(ta, axis=1, keepdims=True)
    exact = qn.astype(np.float64) @ an.astype(np.float64).T
    picked = np.take_along_axis(exact, ix, axis=1)
    best = -np.sort(-exact, axis=1)[:, :k]
    score_err = float(np.abs(sc - picked).max())
    rank_err = float(np.abs(picked - best).max())
    unique = all(len(set(row)) == k for row in ix.tolist())
    res["topk_retrieval"] = {"score_max_abs": score_err,
                             "rank_max_abs": rank_err, "unique": unique}
    say("e", f"topk_retrieval: compiled, score max |err| {score_err:.2e}, "
             f"picked-vs-exact top-{k} max |gap| {rank_err:.2e}")
    check(unique and score_err <= TOPK_TOL and rank_err <= TOPK_TOL,
          "e: topk_retrieval is not a top-k of the exact similarities")
    out["kernels"] = res


def one_chip(args, out) -> None:

    world, data, aset, lib = build_world(args.seed)
    qids = [int(q) for q in data.test_qids]
    t0 = time.perf_counter()
    params = M.init_params(jax.random.PRNGKey(args.seed), CONFIG)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    stats = jax.devices()[0].memory_stats() or {}
    say("b", f"{CONFIG.name}: {n_params:,} params in {CONFIG.dtype}, "
             f"{stats.get('bytes_in_use', 0) / 1e9:.2f} GB in use after "
             f"init ({time.perf_counter() - t0:.1f} s incl. compile)")
    est = Recorder(CONFIG, params, max_new_tokens=BUDGET)
    out["model"] = {"name": CONFIG.name, "params": n_params,
                    "dtype": CONFIG.dtype,
                    "bytes_in_use_after_init": stats.get("bytes_in_use")}

    phase_batch(est, AnchorRetriever(aset), lib, world, data, qids[:4], out)
    phase_stream(est, AnchorRetriever(aset, impl="pallas"), lib, world,
                 data, qids[4:7], qids[7:10], out)
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    del est, params
    gc.collect()
    phase_parity(args.seed, out)
    phase_kernels(args.seed, out)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def four_chips(args, out) -> None:
    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")

    world, data, aset, lib = build_world(args.seed)
    qids = [int(q) for q in data.test_qids[:3]]
    params = M.init_params(jax.random.PRNGKey(args.seed), CONFIG)
    single = Recorder(CONFIG, params, max_new_tokens=BUDGET)
    sharded = Recorder(CONFIG, params, max_new_tokens=BUDGET).shard(
        make_serve_mesh())
    spans = [len(x.sharding.device_set) for x in jax.tree.leaves(
        sharded.params)]
    total = sum(x.nbytes for x in jax.tree.leaves(sharded.params))
    on_dev0 = sum(s.data.nbytes for x in jax.tree.leaves(sharded.params)
                  for s in x.addressable_shards if s.device == devs[0])
    check(min(spans) == 4, f"a param leaf spans {min(spans)} devices")
    share = on_dev0 / total
    check(share < 0.3, f"device 0 holds {share:.2f} of the sharded params")
    say("4", f"sharded params span 4 devices; device 0 holds "
             f"{share:.3f} of {total / 1e9:.2f} GB")

    retriever = AnchorRetriever(aset)
    meta = {m: world.models[m] for m in data.models}
    requests = [RouteRequest([data.queries[q] for q in qids[:2]]),
                RouteRequest([data.queries[qids[2]]])]
    cols, secs = {}, {}
    for name, est in (("single", single), ("sharded", sharded)):
        engine = ScopeEngine.build(EngineConfig(
            estimator=est, retriever=retriever, library=lib,
            models_meta=meta))
        sched = MicrobatchScheduler(BucketConfig(
            batch_sizes=(BUCKET_BATCH,), prompt_lens=(PROMPT_LEN,)))
        t0 = time.perf_counter()
        list(engine.predict_stream(iter(requests), scheduler=sched))
        secs[name] = time.perf_counter() - t0
        check_faults(sched, f"4/{name}")
        cols[name] = cached_pairs(engine, data, qids)
        check_pairs_ok(cols[name], f"4/{name}")
        check(est.logits_finite(), f"4/{name}: non-finite logits")
    # the sharded stream must compute the same numbers: decision logits
    # wherever both had fed identical tokens, at the bf16 tolerance
    pair = [tuple(np.concatenate([np.asarray(c[i]) for c in est.chunks])
                  for i in (0, 1)) for est in (sharded, single)]
    err = R.compare_decodes(*pair)
    agree = decisions_agree(cols["sharded"], cols["single"])
    say("4", f"sharded vs single stream: decision logits rel_rms "
             f"{err['rel_rms']:.2e} rel_max {err['rel_max']:.2e} over "
             f"{err['steps_compared']:.3f} of steps; {err['rows_equal']:.3f}"
             f" of rows generated identical tokens; {agree:.3f} of pairs "
             f"parsed identically; smoke time single {secs['single']:.1f} s,"
             f" sharded {secs['sharded']:.1f} s incl. compile")
    check_close(err, R.TOLERANCE[CONFIG.dtype], "4: sharded vs single")
    check(agree == 1.0, f"4: only {agree:.3f} of pairs parsed identically")
    out["four_chips"] = {"param_share_device0": share,
                         "decision_logits": err, "decisions_agree": agree,
                         "smoke_seconds_incl_compile": secs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        say("a", f"first device is {dev.platform!r}, not a TPU — this smoke "
                 "runs on the chip only")
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say("a", f"device: {device}")
    say("a", f"compile cache: {enable_compile_cache()}")

    out = {"device": device, "chips": args.chips, "seed": args.seed}
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args, out)
        else:
            one_chip(args, out)
    except SmokeFailure as e:
        out["failure"] = str(e)
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        out["smoke_seconds_total"] = time.perf_counter() - t0
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        path = os.path.join(ROOT, "chiprun_out",
                            f"chip_smoke_{args.chips}.json")
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1, default=str)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
