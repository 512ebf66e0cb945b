"""Streaming serve throughput: queries/sec, time-to-first-decision, and
recompile counts for the continuous-batching serve runtime vs naive ragged
dispatch, across bucket configs and device counts.

Sections:

  stream_overlap  — ``ScopeEngine.predict_stream`` over ragged traffic
                    ticks with double-buffered dispatch (microbatch N+1's
                    host assembly overlaps N's device decode); after the
                    bucket warmup, varying per-tick batch sizes must add
                    **zero** new executables (asserted in --smoke)
  stream_sync     — the same stream with ``overlap=False`` (the pre-runtime
                    synchronous loop); the overlap row's qps / ttfd gains
                    are reported against this
  deadline_flush  — paced single-query traffic against an under-filled
                    bucket with ``max_queue_age`` set: partially-filled
                    buckets ship when the latency budget expires, keeping
                    queue age bounded (asserted in --smoke)
  engine_refill   — segment-chunked continuous batching
                    (``predict_stream(refill=True)``): decode runs in
                    fixed-size scan segments and drained-at-EOS rows admit
                    the next queued prompt mid-batch instead of idling
                    until the microbatch retires; measured against
                    ``engine_whole_retire`` (the same stream with
                    ``refill=False``) on a ragged-generation-length
                    workload.  Decode-slot occupancy + refill counters
                    come straight from ``SchedulerStats``; --smoke asserts
                    the refill stream beats whole-retire q/s at higher
                    occupancy with zero recompiles after warmup, and that
                    both streams make identical routing decisions
  engine_chaos    — the refill workload under a deterministic
                    ``FaultPlan``: segment teardowns retry/quarantine,
                    a simulated KV-pool exhaustion fails one row, a parse
                    group is scrambled, and a clock stall blows SLO
                    deadlines; --smoke asserts exactly-once delivery, a
                    consistent fault ledger, zero recompiles after
                    warmup, and that the zero-fault plan is bit-identical
                    to running with no plan at all
  tier0_sweep     — two-tier routing: a tier-0 pre-router head distilled
                    from the engine's own estimator answers high-confidence
                    (query, model) pairs in one jitted forward, and only
                    the rest escalate to the reasoning decode.  The same
                    ragged stream runs at ~0% / ~10% / ~50% / 100%
                    escalation (confidence quantiles of the head); every
                    row carries the scheduler's tier ledger plus decision
                    quality vs the 100%-escalation reference.  --smoke
                    asserts zero recompiles after warmup in every row, the
                    ~10% row at >= 3x the full-reasoning q/s, and — with
                    caching on — that threshold > 1 is bit-identical to
                    running without a tier-0 head at all (predictions,
                    cache contents, deterministic scheduler stats modulo
                    the tier ledger)
  engine_drift    — drift-aware self-healing closed loop: a ``model_drift``
                    fault corrupts one model's served outcomes mid-stream;
                    the Page–Hinkley monitor over calibration residuals
                    must alarm within a few ticks, quarantine the model,
                    re-fingerprint it from the replay buffer, and hot-swap
                    the estimator version live.  --smoke asserts the
                    detector-on no-fault stream is bit-identical to
                    detector-off (collection is passive), the alarm fires
                    within 4 ticks of the drift, post-heal decisions match
                    a clean engine on the healed state, the outcome ledger
                    balances, and warmup onward adds zero executables
  stream_naive    — ``predict`` called per ragged tick (the pre-scheduler
                    behavior): every distinct tick size compiles a fresh
                    (batch, len) executable
  batch_oracle    — one big ``predict`` over all queries (the throughput
                    ceiling a scheduler can approach); --smoke also
                    asserts the stream results are bit-identical to it
  sharded         — bucketed stream with the estimator sharded over the
                    serve mesh (only when >1 device is visible; multiply
                    CPU devices with
                    XLA_FLAGS=--xla_force_host_platform_device_count=N or
                    the --devices flag, which sets it before jax loads)

Rows go to stdout CSV (via ``benchmarks.run``) and to
``benchmarks/BENCH_serve_throughput.json``.  Standalone:

  PYTHONPATH=src python benchmarks/bench_serve_throughput.py --smoke
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Dict, List, Tuple

import numpy as np

BENCH_PATH = os.path.join(os.path.dirname(__file__),
                          "BENCH_serve_throughput.json")


def _tick_sizes(n_queries: int, seed: int = 0, max_tick: int = 8) -> List[int]:
    """Deterministic ragged traffic: tick sizes in [1, max_tick]."""
    rng = np.random.default_rng(seed)
    sizes, left = [], n_queries
    while left > 0:
        s = int(rng.integers(1, max_tick + 1))
        sizes.append(min(s, left))
        left -= sizes[-1]
    return sizes


def _as_ticks(queries, sizes):
    out, i = [], 0
    for s in sizes:
        out.append(queries[i: i + s])
        i += s
    return out


def _compile_delta(before: Dict[str, int], after: Dict[str, int]) -> int:
    return sum(after[k] - before[k] for k in after)


# ---------------------------------------------------------------------------
# Sections
# ---------------------------------------------------------------------------
def _stream_once(engine, ticks, cfg, *, overlap: bool):
    """One full stream pass; returns (pools, total_s, ttfd_s, scheduler)."""
    from repro.api import RouteRequest
    from repro.serving.scheduler import MicrobatchScheduler
    sched = MicrobatchScheduler(cfg)
    t0 = time.perf_counter()
    it = engine.predict_stream((RouteRequest(t) for t in ticks),
                               scheduler=sched, use_cache=False,
                               overlap=overlap)
    first = next(it)
    ttfd = time.perf_counter() - t0
    pools = [first] + list(it)
    return pools, time.perf_counter() - t0, ttfd, sched


def bench_stream(engine, queries, *, bucket_sizes, repeats: int = 3,
                 max_tick: int = 8, smoke: bool = False) -> List[Dict]:
    from repro.api import RouteRequest
    from repro.serving.scheduler import BucketConfig, decode_compile_counts

    sizes = _tick_sizes(len(queries), max_tick=max_tick)
    ticks = _as_ticks(queries, sizes)
    n_models = len(engine.registry.routable())

    # -- bucketed stream: warm the bucket executables, then measure ----
    cfg = BucketConfig(batch_sizes=bucket_sizes)
    _stream_once(engine, ticks, cfg, overlap=True)
    warmed = decode_compile_counts()

    def measure(overlap):
        times, ttfds, pools, sched = [], [], None, None
        for _ in range(repeats):
            pools, dt, ttfd, sched = _stream_once(engine, ticks, cfg,
                                                  overlap=overlap)
            times.append(dt)
            ttfds.append(ttfd)
        return pools, len(queries) / min(times), min(ttfds), sched

    # sync first so progressive warming cannot flatter the overlap row
    sync_pools, qps_sync, ttfd_sync, _ = measure(False)
    overlap_pools, qps_overlap, ttfd_overlap, sched = measure(True)
    recompiles = _compile_delta(warmed, decode_compile_counts())

    # -- naive ragged dispatch: one predict per tick -------------------
    before = decode_compile_counts()
    t0 = time.perf_counter()
    naive_pools = [engine.predict(RouteRequest(t), use_cache=False)
                   for t in ticks]
    t_naive = time.perf_counter() - t0
    naive_recompiles = _compile_delta(before, decode_compile_counts())
    qps_naive = len(queries) / t_naive

    # -- batch oracle: the whole query set in one predict (warm shape) -
    engine.predict(RouteRequest(list(queries)), use_cache=False)
    t0 = time.perf_counter()
    batch_pool = engine.predict(RouteRequest(list(queries)), use_cache=False)
    t_batch = time.perf_counter() - t0
    qps_batch = len(queries) / t_batch

    overlap_p = np.concatenate([p.p_hat for p in overlap_pools])
    sync_p = np.concatenate([p.p_hat for p in sync_pools])
    naive_p = np.concatenate([p.p_hat for p in naive_pools])
    identical_stream = bool(np.array_equal(overlap_p, batch_pool.p_hat))
    identical_sync = bool(np.array_equal(sync_p, batch_pool.p_hat))
    identical_naive = bool(np.array_equal(naive_p, batch_pool.p_hat))
    if smoke:
        assert recompiles == 0, (
            f"stream runtime recompiled {recompiles} executables after "
            f"warmup — each (bucket, shape) must compile exactly once")
        assert identical_stream, "overlap stream p_hat != batch predict"
        assert identical_sync, "sync stream p_hat != batch predict"

    st = sched.stats.as_dict()
    return [
        {"name": "serve_throughput/stream_overlap", "qps": qps_overlap,
         "detail": {"ticks": len(ticks), "queries": len(queries),
                    "models": n_models, "buckets": st["buckets"],
                    "pad_fraction": st["pad_fraction"],
                    "microbatches": st["microbatches"],
                    "ttfd_ms": round(ttfd_overlap * 1e3, 2),
                    "queue_age_ms": st["queue_age_ms"],
                    "recompiles_after_warmup": recompiles,
                    "speedup_vs_sync":
                        round(qps_overlap / max(qps_sync, 1e-9), 3),
                    "identical_to_batch": identical_stream}},
        {"name": "serve_throughput/stream_sync", "qps": qps_sync,
         "detail": {"ticks": len(ticks),
                    "ttfd_ms": round(ttfd_sync * 1e3, 2),
                    "identical_to_batch": identical_sync}},
        {"name": "serve_throughput/stream_naive", "qps": qps_naive,
         "detail": {"ticks": len(ticks),
                    "distinct_tick_sizes": len(set(sizes)),
                    "recompiles": naive_recompiles,
                    "identical_to_batch": identical_naive}},
        {"name": "serve_throughput/batch_oracle", "qps": qps_batch,
         "detail": {"queries": len(queries),
                    "speedup_stream_vs_naive":
                        round(qps_overlap / max(qps_naive, 1e-9), 2)}},
    ]


def bench_deadline(engine, queries, *, full_bucket: int = 16,
                   max_queue_ms: float = 5.0, inter_arrival_ms: float = 1.0,
                   smoke: bool = False) -> List[Dict]:
    """Paced single-query traffic against an under-filled bucket.

    Each tick contributes (1 query x M models) prompts — far short of the
    ``full_bucket`` batch — so without a deadline nothing would ship until
    stream end.  With ``max_queue_age`` set, ``tick()`` emits
    partially-filled buckets the moment the oldest prompt ages out.  The
    deadline is **tick-granular**: ticks fire on request arrival in the
    single-threaded drain loop, so realized queue age is bounded by
    ``max_queue_age`` plus the time to the next tick (including any
    microbatch execution the loop blocks on) — the warmup pass below keeps
    one-off XLA compiles out of the measured ages.
    """
    from repro.api import RouteRequest
    from repro.serving.scheduler import BucketConfig, MicrobatchScheduler

    def paced():
        for q in queries:
            time.sleep(inter_arrival_ms / 1e3)
            yield RouteRequest([q])

    def run():
        sched = MicrobatchScheduler(
            BucketConfig(batch_sizes=(full_bucket,)),
            max_queue_age=max_queue_ms / 1e3)
        t0 = time.perf_counter()
        pools = list(engine.predict_stream(paced(), scheduler=sched,
                                           use_cache=False))
        return pools, time.perf_counter() - t0, sched

    run()                       # warm the (full/partial bucket) executables
    pools, dt, sched = run()
    st = sched.stats
    ages = st.queue_age_percentiles()
    # steady-state bound: deadline + a handful of warm microbatch
    # executions the drain loop may block on before the next tick
    exec_ms = dt * 1e3 / max(st.microbatches, 1)
    bound_ms = max_queue_ms + 4 * exec_ms
    if smoke:
        assert st.deadline_flushes > 0, (
            "deadline never fired: paced sub-bucket traffic must trigger "
            "max_queue_age partial flushes")
        assert st.partial_microbatches > 0, (
            "no partially-filled buckets were emitted under the deadline")
        assert len(pools) == len(queries)
        assert ages["max"] * 1e3 <= bound_ms, (
            f"warm queue age {ages['max'] * 1e3:.1f}ms exceeds the "
            f"tick-granular bound {bound_ms:.1f}ms")
    return [{
        "name": "serve_throughput/deadline_flush",
        "qps": len(queries) / dt,
        "detail": {"max_queue_ms": max_queue_ms,
                   "inter_arrival_ms": inter_arrival_ms,
                   "full_bucket": full_bucket,
                   "deadline_flushes": st.deadline_flushes,
                   "partial_microbatches": st.partial_microbatches,
                   "microbatches": st.microbatches,
                   "pad_fraction": round(st.pad_fraction, 4),
                   "age_bound_ms": round(bound_ms, 2),
                   "queue_age_ms": {k: round(v * 1e3, 2)
                                    for k, v in ages.items()}}}]


def bench_refill(engine, queries, *, bucket_sizes, segment_len: int = 4,
                 repeats: int = 3, max_tick: int = 3,
                 smoke: bool = False) -> List[Dict]:
    """Segment-chunked slot refill vs whole-retire on a ragged workload.

    ``engine`` must carry an EOS-emitting (trained) estimator: rows then
    drain at different decode steps, which is the regime where mid-batch
    refill pays — ``refill=True`` admits the oldest queued prompt into a
    drained slot between scan segments, while ``refill=False`` idles the
    slot until the whole microbatch retires.  Occupancy and refill
    counters are read straight from ``SchedulerStats`` (both modes account
    ``slot_steps_active/total`` at token granularity, so the comparison is
    one counter pair, not a recompute).  Routing-decision identity between
    the two modes is checked on every field the router consumes:
    token-derived fields bit-equal, confidences to f32 ulp, and the final
    ``FixedAlphaPolicy`` choices equal.
    """
    from repro.api import FixedAlphaPolicy, RouteRequest
    from repro.serving.scheduler import BucketConfig, MicrobatchScheduler
    from repro.serving.scheduler import decode_compile_counts

    seg = max(1, min(segment_len, int(engine.estimator.max_new_tokens)))
    ticks = _as_ticks(queries, _tick_sizes(len(queries), max_tick=max_tick))
    cfg = BucketConfig(batch_sizes=bucket_sizes)

    def stream(refill):
        sched = MicrobatchScheduler(cfg)
        t0 = time.perf_counter()
        pools = list(engine.predict_stream(
            (RouteRequest(t) for t in ticks), scheduler=sched,
            use_cache=False, refill=refill, segment_len=seg))
        return pools, time.perf_counter() - t0, sched

    stream(False)                   # warm both modes' executables
    stream(True)
    warmed = decode_compile_counts()

    # interleaved pairs (off, on) so wall-clock drift on a shared machine
    # hits both modes alike; best-of per mode
    t_off = t_on = None
    off_pools = on_pools = s_off = s_on = None
    for _ in range(repeats):
        off_pools, dt, s_off = stream(False)
        t_off = dt if t_off is None else min(t_off, dt)
        on_pools, dt, s_on = stream(True)
        t_on = dt if t_on is None else min(t_on, dt)
    recompiles = _compile_delta(warmed, decode_compile_counts())
    qps_off = len(queries) / t_off
    qps_on = len(queries) / t_on

    def cat(pools, field):
        return np.concatenate([np.asarray(getattr(p, field)).reshape(-1)
                               for p in pools])

    token_identical = all(
        np.array_equal(cat(on_pools, f), cat(off_pools, f))
        for f in ("y_hat", "len_hat", "well_formed", "cost_hat",
                  "pred_overhead"))
    conf_close = bool(np.allclose(cat(on_pools, "p_hat"),
                                  cat(off_pools, "p_hat"),
                                  atol=1e-6, rtol=1e-6))
    policy = FixedAlphaPolicy(0.6)
    choices_on = np.concatenate(
        [np.asarray(policy.decide(p, engine).choices) for p in on_pools])
    choices_off = np.concatenate(
        [np.asarray(policy.decide(p, engine).choices) for p in off_pools])
    identical_decisions = bool(np.array_equal(choices_on, choices_off))

    st_on, st_off = s_on.stats, s_off.stats
    if smoke:
        assert recompiles == 0, (
            f"refill stream recompiled {recompiles} executables after "
            f"warmup — segments and refill prefills must reuse the warmed "
            f"bucket shapes")
        assert token_identical, (
            "refill-on vs refill-off streams disagree on token-derived "
            "prediction fields")
        assert conf_close, "refill-on vs refill-off confidences diverge"
        assert identical_decisions, (
            "refill-on vs refill-off streams routed differently")
        assert st_on.slots_refilled > 0, (
            "no slot was refilled: the ragged workload must drain rows "
            "at EOS mid-batch")
        assert st_on.slot_occupancy > st_off.slot_occupancy, (
            f"refill occupancy {st_on.slot_occupancy:.3f} does not beat "
            f"whole-retire {st_off.slot_occupancy:.3f}")
        assert st_on.slot_steps_total < st_off.slot_steps_total, (
            f"refill ran {st_on.slot_steps_total} decode slot-steps vs "
            f"whole-retire's {st_off.slot_steps_total} for identical "
            "output — the deterministic work saving disappeared")
        assert qps_on > qps_off, (
            f"refill q/s {qps_on:.2f} does not beat whole-retire "
            f"{qps_off:.2f} on the ragged workload")
    return [
        {"name": "serve_throughput/engine_refill", "qps": qps_on,
         "detail": {"queries": len(queries), "ticks": len(ticks),
                    "segment_len": seg,
                    "slot_occupancy": round(st_on.slot_occupancy, 4),
                    "slots_refilled": st_on.slots_refilled,
                    "refill_steps_saved": st_on.refill_steps_saved,
                    "slot_steps": st_on.slot_steps_total,
                    "recompiles_after_warmup": recompiles,
                    "speedup_vs_whole_retire":
                        round(qps_on / max(qps_off, 1e-9), 3),
                    "identical_decisions": identical_decisions}},
        {"name": "serve_throughput/engine_whole_retire", "qps": qps_off,
         "detail": {"queries": len(queries),
                    "slot_occupancy": round(st_off.slot_occupancy, 4),
                    "slot_steps": st_off.slot_steps_total,
                    "identical_decisions": identical_decisions}},
    ]


def bench_paged(engine, paged_engine, queries, *, bucket_sizes,
                segment_len: int = 4, repeats: int = 3, max_tick: int = 3,
                smoke: bool = False) -> List[Dict]:
    """Paged slots at a small page size vs whole-retire, same workload.

    Both engines carry the same trained parameters and stream the same
    ragged workload: ``engine`` retires whole microbatches
    (``refill=False``, one-shot dense caches), ``paged_engine`` refills
    paged slots (``refill=True``) at its own ``kv_page_size``.  The paged
    XLA gather path reconstructs exactly the contiguous cache the dense
    kernel reads, so every token-derived field must be bit-equal and the
    final routing decisions identical; the row reports what the page pool
    held (``pages_peak``, ``kv_peak_tokens``).
    """
    from repro.api import FixedAlphaPolicy, RouteRequest
    from repro.serving.scheduler import BucketConfig, MicrobatchScheduler
    from repro.serving.scheduler import decode_compile_counts

    seg = max(1, min(segment_len, int(engine.estimator.max_new_tokens)))
    ticks = _as_ticks(queries, _tick_sizes(len(queries), max_tick=max_tick))
    cfg = BucketConfig(batch_sizes=bucket_sizes)

    def stream(eng, refill):
        sched = MicrobatchScheduler(cfg)
        t0 = time.perf_counter()
        pools = list(eng.predict_stream(
            (RouteRequest(t) for t in ticks), scheduler=sched,
            use_cache=False, refill=refill, segment_len=seg))
        return pools, time.perf_counter() - t0, sched

    stream(engine, False)           # warm both paths' executables
    stream(paged_engine, True)
    warmed = decode_compile_counts()

    t_whole = t_paged = None
    whole_pools = paged_pools = s_paged = None
    for _ in range(repeats):
        whole_pools, dt, _ = stream(engine, False)
        t_whole = dt if t_whole is None else min(t_whole, dt)
        paged_pools, dt, s_paged = stream(paged_engine, True)
        t_paged = dt if t_paged is None else min(t_paged, dt)
    recompiles = _compile_delta(warmed, decode_compile_counts())
    qps_whole = len(queries) / t_whole
    qps_paged = len(queries) / t_paged

    def cat(pools, field):
        return np.concatenate([np.asarray(getattr(p, field)).reshape(-1)
                               for p in pools])

    token_identical = all(
        np.array_equal(cat(paged_pools, f), cat(whole_pools, f))
        for f in ("y_hat", "len_hat", "well_formed", "cost_hat",
                  "pred_overhead"))
    conf_close = bool(np.allclose(cat(paged_pools, "p_hat"),
                                  cat(whole_pools, "p_hat"),
                                  atol=1e-6, rtol=1e-6))
    policy = FixedAlphaPolicy(0.6)
    choices_paged = np.concatenate(
        [np.asarray(policy.decide(p, engine).choices) for p in paged_pools])
    choices_whole = np.concatenate(
        [np.asarray(policy.decide(p, engine).choices) for p in whole_pools])
    identical_decisions = bool(np.array_equal(choices_paged, choices_whole))

    st_p = s_paged.stats
    if smoke:
        assert recompiles == 0, (
            f"paged stream recompiled {recompiles} executables after "
            f"warmup — page tables are traced, so steady-state segments "
            f"must reuse the warmed bucket shapes")
        assert token_identical, (
            "paged vs whole-retire streams disagree on token-derived "
            "prediction fields — the gather path lost bit parity")
        assert conf_close, "paged vs whole-retire confidences diverge"
        assert identical_decisions, (
            "paged vs whole-retire streams routed differently")
        assert st_p.pages_peak > 0 and st_p.kv_page_size > 0, (
            "the paged stream never touched the page pool")
    return [
        {"name": "serve_throughput/engine_paged", "qps": qps_paged,
         "detail": {"queries": len(queries), "segment_len": seg,
                    "kv_page_size": st_p.kv_page_size,
                    "pages_peak": st_p.pages_peak,
                    "kv_peak_tokens": st_p.kv_peak_tokens,
                    "page_fragmentation":
                        round(st_p.page_fragmentation, 4),
                    "deferred_on_pages":
                        st_p.admissions_deferred_on_pages,
                    "recompiles_after_warmup": recompiles,
                    "qps_vs_whole_retire":
                        round(qps_paged / max(qps_whole, 1e-9), 3),
                    "identical_decisions": identical_decisions}},
    ]


def bench_chaos(engine, queries, *, bucket_sizes, segment_len: int = 4,
                smoke: bool = False) -> List[Dict]:
    """Fault-tolerant serving under a deterministic chaos plan.

    Runs the ``engine_refill`` workload three ways on the paged engine:
    no fault plan at all, ``FaultPlan.none()`` (must be bit-identical —
    the asserted no-op), and a deterministic chaos plan mixing segment
    teardowns (bounded retry + quarantine), a simulated KV-pool row
    failure, a scrambled parse group, and one huge clock stall that blows
    the SLO deadline of every prompt in flight.  Under chaos the smoke
    gate asserts exactly-once delivery (every (query, model) pair answered
    once), ledger consistency (non-OK pairs == degraded + failed ==
    quarantined + deadline-expired prompts), and zero recompiles after
    warmup — the retry/requeue machinery must reuse the warmed bucket
    shapes, never invent new ones.
    """
    from repro.api import RouteRequest
    from repro.core.status import STATUS_OK
    from repro.serving.faults import FaultPlan, FaultSpec
    from repro.serving.scheduler import BucketConfig, MicrobatchScheduler
    from repro.serving.scheduler import decode_compile_counts

    seg = max(1, min(segment_len, int(engine.estimator.max_new_tokens)))
    ticks = _as_ticks(queries, _tick_sizes(len(queries), max_tick=3))
    cfg = BucketConfig(batch_sizes=bucket_sizes)
    n_models = len(engine.registry.routable())

    def stream():
        sched = MicrobatchScheduler(cfg)
        t0 = time.perf_counter()
        pools = list(engine.predict_stream(
            (RouteRequest(t) for t in ticks), scheduler=sched,
            use_cache=False, refill=True, segment_len=seg))
        return pools, time.perf_counter() - t0, sched

    def cat(pools, field):
        return np.concatenate([np.asarray(getattr(p, field)).reshape(-1)
                               for p in pools])

    # -- the asserted no-op: an empty plan must not perturb the stream --
    engine.config.fault_plan = None
    base_pools, _, _ = stream()
    engine.config.fault_plan = FaultPlan.none()
    none_pools, _, _ = stream()
    noop_identical = all(
        np.array_equal(cat(none_pools, f), cat(base_pools, f))
        for f in ("p_hat", "y_hat", "len_hat", "well_formed", "cost_hat",
                  "pred_overhead", "status"))

    # -- deterministic chaos: replays identically on identical traffic -
    chaos = FaultPlan([FaultSpec("segment", 1), FaultSpec("segment", 2),
                       FaultSpec("segment", 4),
                       FaultSpec("pool", 3, arg=1.0),
                       FaultSpec("parse", 2),
                       FaultSpec("stall", 8, arg=1e6)])
    engine.config.fault_plan = chaos
    engine.config.deadline_ms = 60_000.0
    engine.config.max_retries = 1
    try:
        stream()                        # warm the retry/flush shapes
        warmed = decode_compile_counts()
        pools, dt, sched = stream()
        recompiles = _compile_delta(warmed, decode_compile_counts())
    finally:
        engine.config.fault_plan = None
        engine.config.deadline_ms = None
        engine.config.max_retries = 2
    st = sched.stats
    status = cat(pools, "status")
    n_pairs = len(queries) * n_models
    exactly_once = status.size == n_pairs
    n_degraded = int((status != STATUS_OK).sum())
    ledger_consistent = (
        n_degraded == st.degraded + st.failed_pairs
        == st.quarantined + st.deadline_expired)
    if smoke:
        assert noop_identical, (
            "FaultPlan.none() perturbed the stream — the zero-fault path "
            "must be bit-identical to running without a plan")
        assert exactly_once, (
            f"chaos stream answered {status.size} pairs for {n_pairs} "
            f"submitted — exactly-once delivery broke")
        assert st.injected_faults > 0 and st.retries > 0, (
            "the chaos plan never fired / never reached the retry path")
        assert st.quarantined > 0, (
            "no prompt exhausted max_retries under repeated segment faults")
        assert st.deadline_expired > 0, (
            "the injected clock stall expired no deadlines")
        assert st.kv_exhausted_rows > 0, (
            "the injected pool fault failed no row")
        assert n_degraded > 0 and ledger_consistent, (
            f"fault ledger inconsistent: {n_degraded} non-OK pairs, "
            f"degraded={st.degraded} failed={st.failed_pairs} "
            f"quarantined={st.quarantined} "
            f"deadline_expired={st.deadline_expired}")
        assert recompiles == 0, (
            f"chaos stream recompiled {recompiles} executables after "
            f"warmup — retries and requeues must reuse the warmed bucket "
            f"shapes")
    return [{
        "name": "serve_throughput/engine_chaos",
        "qps": len(queries) / dt,
        "detail": {"queries": len(queries), "pairs": n_pairs,
                   "injected_faults": st.injected_faults,
                   "retries": st.retries, "requeued": st.requeued,
                   "quarantined": st.quarantined,
                   "deadline_expired": st.deadline_expired,
                   "kv_exhausted_rows": st.kv_exhausted_rows,
                   "degraded_fraction": round(st.degraded_fraction, 4),
                   "noop_identical": noop_identical,
                   "exactly_once": exactly_once,
                   "ledger_consistent": ledger_consistent,
                   "recompiles_after_warmup": recompiles}}]


def bench_drift(mk, data, *, bucket_sizes,
                n_queries: int = 16, tick_size: int = 4, n_ticks: int = 10,
                smoke: bool = False) -> List[Dict]:
    """Drift-aware self-healing: inject -> detect -> quarantine -> refresh
    -> recover, closed loop over served traffic.

    Four streams over the same cycled qid ticks (``n_ticks`` ticks of
    ``tick_size``, cycling ``n_queries`` qids so the victim model
    accumulates observations):

      1. detector-off reference;
      2. detector-on, no fault — the asserted no-op: decisions, cache
         contents, and deterministic scheduler stats outside the drift
         block must be bit-identical to (1), collection is passive.  Its
         monitor ledger also picks the *victim*: the model with the most
         well-formed served observations, so drift events land on rows
         the detector scores;
      3. the drift run: a ``model_drift`` fault forces the victim's
         observed outcomes wrong from event K on.  The Page–Hinkley
         detector must alarm within a few ticks; at the alarm tick the
         loop heals live — ``onboard(refresh=True)`` re-fingerprints the
         victim from the replay buffer's observed outcomes (no offline
         dataset) and ``hot_swap`` bumps the estimator version mid-stream
         — and the stream keeps serving;
      4. a clean engine over the same ticks against the *refreshed*
         library: every post-heal tick of (3) must make identical routing
         decisions — the healed serve path converged to what a fresh
         engine computes from the healed state.

    Streams run whole-retire with ``overlap=False`` so tick boundaries
    align with prompt serialization and the recovery comparison is exact
    (the refill runtime serializes ticks ahead of their reports; swap
    correctness *inside* a refill stream is covered by the engine tests).
    --smoke additionally asserts exactly-once delivery (every tick answers
    exactly its queries; the replay buffer holds one row per executed
    query) and zero recompiles after warmup across the drift run and the
    recovery reference — healing swaps fingerprint *values* and the
    params pointer, never shapes.
    """
    from repro.api import FixedAlphaPolicy
    from repro.serving.faults import FaultPlan, FaultSpec
    from repro.serving.scheduler import BucketConfig, MicrobatchScheduler
    from repro.serving.scheduler import decode_compile_counts

    world = data.world
    policy = FixedAlphaPolicy(0.6)
    cfg = BucketConfig(batch_sizes=bucket_sizes)
    qids = [int(q) for q in data.test_qids[:n_queries]]
    ticks = [[qids[(t * tick_size + j) % len(qids)]
              for j in range(tick_size)] for t in range(n_ticks)]
    n_served = n_ticks * tick_size
    drift_at = 2 * tick_size            # event index: first query of tick 2
    # alarm-fast knobs for the injected run only; the no-op identity
    # stream (2) keeps the defaults so clean traffic can't false-alarm
    sensitive = dict(drift_detect=True, drift_threshold=2.5,
                     drift_delta=0.05, drift_min_obs=3)

    def serve(eng, *, use_cache, on_tick=None):
        sched = MicrobatchScheduler(cfg)
        reports = []
        t0 = time.perf_counter()
        for i, r in enumerate(eng.serve_stream(
                data, [list(t) for t in ticks], policy, scheduler=sched,
                use_cache=use_cache, overlap=False, refill=False)):
            reports.append(r)
            if on_tick is not None:
                on_tick(eng, i)
        return reports, time.perf_counter() - t0, sched

    def tick_models(reports):
        return [[d.model for d in r.decisions] for r in reports]

    # -- (1) detector-off reference --------------------------------------
    eng_off = mk()
    off_reports, _, s_off = serve(eng_off, use_cache=True)

    # -- (2) passive collection: detector-on == detector-off ------------
    eng_on = mk(drift_detect=True)
    on_reports, _, s_on = serve(eng_on, use_cache=True)

    # the victim comes from the monitor's own ledger: the model with the
    # most *well-formed* served observations (malformed parse-fallback
    # rows are buffered but never scored, so drift events must land on
    # rows the detector actually sees)
    wf_share: Dict[str, int] = {}
    for row in eng_on.monitor.buffer.rows():
        if row.well_formed:
            wf_share[row.model] = wf_share.get(row.model, 0) + 1
    victim = max(sorted(wf_share), key=lambda m: wf_share[m])

    def det_stats(sched):
        return {k: v for k, v in sched.stats.as_dict().items()
                if k not in ("queue_age_ms", "drift")}

    noop_decisions = tick_models(on_reports) == tick_models(off_reports)
    noop_cache = eng_on.cache._store == eng_off.cache._store
    noop_stats = det_stats(s_on) == det_stats(s_off)

    # -- (3) the drift run: inject, detect, heal live --------------------
    plan = FaultPlan([FaultSpec("model_drift", drift_at, arg=1.0,
                                model=victim)])
    eng_d = mk(fault_plan=plan, **sensitive)
    fp_before = eng_d.library.get(victim)
    fp_mean_before = float(np.mean(fp_before.y))
    state = {"alarm_tick": None, "heal_tick": None}

    def heal(eng, i):
        if state["alarm_tick"] is not None:
            return
        if victim not in eng.monitor.drifted:
            return
        state["alarm_tick"] = i
        # live heal between ticks: replay-buffer re-fingerprint (no
        # offline dataset) + estimator hot-swap under a bumped version
        eng.onboard(world, victim, refresh=True)
        eng.hot_swap(eng.estimator,
                     eng.config.estimator_version + "+heal")
        state["heal_tick"] = i

    warmed = decode_compile_counts()
    try:
        d_reports, dt, s_d = serve(eng_d, use_cache=False, on_tick=heal)
        fp_mean_after = float(np.mean(eng_d.library.get(victim).y))

        # -- (4) recovery reference: clean engine, healed library --------
        clean_reports, _, _ = serve(mk(), use_cache=False)
    finally:
        # the heal mutated the *shared* fingerprint library (that sharing
        # is what lets (4) see the refresh); put the original back so
        # later benches see pristine fingerprints
        eng_d.library.add(fp_before)
    recompiles = _compile_delta(warmed, decode_compile_counts())

    alarm_tick, heal_tick = state["alarm_tick"], state["heal_tick"]
    drift_tick = drift_at // tick_size
    post = (heal_tick + 1) if heal_tick is not None else len(ticks)
    recovered = (tick_models(d_reports)[post:]
                 == tick_models(clean_reports)[post:])
    dst = s_d.stats
    ledger_balanced = (
        sum(r.n_queries for r in d_reports) == n_served
        and all(len(r.decisions) == len(t)
                for r, t in zip(d_reports, ticks, strict=True))
        and dst.replay_buffer_len == n_served)
    if smoke:
        assert noop_decisions and noop_cache and noop_stats, (
            f"detector-on serving with no drift fault diverged from "
            f"detector-off (decisions equal: {noop_decisions}, cache "
            f"equal: {noop_cache}, stats equal: {noop_stats}) — outcome "
            f"collection must be passive")
        assert s_on.stats.drift_alarms == 0, (
            "the detector false-alarmed on clean traffic")
        assert s_on.stats.replay_buffer_len == n_served, (
            f"detector-on stream buffered {s_on.stats.replay_buffer_len} "
            f"outcomes for {n_served} served queries")
        assert alarm_tick is not None, (
            f"the drift detector never fired on {victim!r} drifting at "
            f"tick {drift_tick}")
        assert alarm_tick - drift_tick <= 4, (
            f"detector fired at tick {alarm_tick}, "
            f"{alarm_tick - drift_tick} ticks after the drift at tick "
            f"{drift_tick} — the closed loop is too slow")
        assert fp_mean_after < fp_mean_before, (
            f"replay-buffer refresh did not move the victim fingerprint "
            f"({fp_mean_before:.3f} -> {fp_mean_after:.3f})")
        assert recovered, (
            "post-heal ticks routed differently from a clean engine on "
            "the healed state — the swap/refresh left stale serve state")
        assert ledger_balanced, (
            f"drift ledger does not balance: "
            f"{sum(r.n_queries for r in d_reports)} answered for "
            f"{n_served} served, buffer {dst.replay_buffer_len}")
        assert dst.drift_alarms >= 1 and dst.hot_swaps == 1, (
            f"drift stats block wrong: alarms={dst.drift_alarms} "
            f"hot_swaps={dst.hot_swaps}")
        assert recompiles == 0, (
            f"the drift run recompiled {recompiles} executables after "
            f"warmup — fingerprint refresh and hot-swap must never "
            f"change shapes")
    return [{
        "name": "serve_throughput/engine_drift",
        "qps": n_served / dt,
        "detail": {"queries": n_served, "victim": victim,
                   "drift_tick": drift_tick, "alarm_tick": alarm_tick,
                   "ticks_to_alarm": (None if alarm_tick is None
                                      else alarm_tick - drift_tick),
                   "victim_fp_mean": [round(fp_mean_before, 3),
                                      round(fp_mean_after, 3)],
                   "noop_identical": bool(noop_decisions and noop_cache
                                          and noop_stats),
                   "recovered_decisions": recovered,
                   "ledger_balanced": ledger_balanced,
                   "drift": s_d.stats.as_dict()["drift"],
                   "recompiles_after_warmup": recompiles}}]


def bench_tier0(engine, queries, *, bucket_sizes, data, mk,
                distill_steps: int = 200, max_pairs: int = 1200,
                repeats: int = 2, smoke: bool = False) -> List[Dict]:
    """Escalation-threshold sweep for two-tier routing + identity gate.

    A tier-0 pre-router head is distilled from ``engine``'s own estimator
    (teacher labels come from the reasoning decode's parsed outputs), then
    the same ragged stream runs at four escalation thresholds: 0 (every
    pair answered by the head), the head's 10% and 50% confidence
    quantiles over this exact workload (~10% / ~50% of pairs escalate),
    and 2.0 (every pair pays the full reasoning decode — the reference
    row).  Tier-0 answered pairs never enter the microbatch scheduler, so
    the q/s gain tracks the decode tokens the ledger says were saved.

    Decision quality is measured against the 100%-escalation reference:
    ``FixedAlphaPolicy`` choice agreement and confidence MAE per row.
    The separate identity check streams with caching *on* through two
    fresh engines — tier-0 at threshold 2.0 vs no tier-0 at all — and
    compares every prediction field, the cache stores, and the
    deterministic scheduler stats (everything except wall-clock queue
    ages and the new tier ledger); --smoke asserts all of it.
    """
    from benchmarks.common import tier_ledger
    from repro.api import FixedAlphaPolicy, RouteRequest
    from repro.serving.scheduler import BucketConfig, MicrobatchScheduler
    from repro.serving.scheduler import decode_compile_counts
    from repro.training.tier0 import distill_tier0

    head = distill_tier0(data, engine.config.library,
                         engine.config.retriever, engine.estimator,
                         max_pairs=max_pairs, steps=distill_steps, seed=0)
    ticks = _as_ticks(queries, _tick_sizes(len(queries), max_tick=3))
    cfg = BucketConfig(batch_sizes=bucket_sizes)
    n_pairs = len(queries) * len(engine.registry.routable())

    def stream(eng, *, use_cache=False):
        sched = MicrobatchScheduler(cfg)
        t0 = time.perf_counter()
        pools = list(eng.predict_stream(
            (RouteRequest(t) for t in ticks), scheduler=sched,
            use_cache=use_cache))
        return pools, time.perf_counter() - t0, sched

    def cat(pools, field):
        return np.concatenate([np.asarray(getattr(p, field)).reshape(-1)
                               for p in pools])

    # head confidences over this exact workload: at threshold 0 every
    # pair is answered by the head, so p_hat IS the calibrated tier-0
    # probability and max(p, 1-p) the escalation signal the gate sees
    policy = FixedAlphaPolicy(0.6)
    results = {}
    try:
        engine.config.tier0 = head
        engine.config.escalation_threshold = 0.0
        probe_pools, _, _ = stream(engine)
        p0 = cat(probe_pools, "p_hat")
        conf = np.maximum(p0, 1.0 - p0)
        sweep = [("esc_0", 0.0),
                 ("esc_10", float(np.quantile(conf, 0.10))),
                 ("esc_50", float(np.quantile(conf, 0.50))),
                 ("esc_100", 2.0)]
        for tag, thr in sweep:
            engine.config.escalation_threshold = thr
            stream(engine)          # warm this row's decode bucket shapes
            warmed = decode_compile_counts()
            best = pools = sched = None
            for _ in range(repeats):
                pools, dt, sched = stream(engine)
                best = dt if best is None else min(best, dt)
            choices = np.concatenate(
                [np.asarray(policy.decide(p, engine).choices)
                 for p in pools])
            results[tag] = {
                "thr": thr, "qps": len(queries) / best, "pools": pools,
                "stats": sched.stats, "choices": choices,
                "recompiles": _compile_delta(warmed,
                                             decode_compile_counts())}
    finally:
        engine.config.tier0 = None
        engine.config.escalation_threshold = 0.9

    # -- identity gate: threshold > 1 must equal no tier-0 head at all --
    ref_eng, t0_eng = mk(), mk(tier0=head, escalation_threshold=2.0)
    ref_pools, _, ref_sched = stream(ref_eng, use_cache=True)
    t0_pools, dt_id, t0_sched = stream(t0_eng, use_cache=True)
    fields = ("p_hat", "y_hat", "len_hat", "well_formed", "cost_hat",
              "pred_overhead", "status")
    identical_fields = all(
        np.array_equal(cat(t0_pools, f), cat(ref_pools, f))
        for f in fields)
    identical_cache = t0_eng.cache._store == ref_eng.cache._store

    def det_stats(sched_stats):
        return {k: v for k, v in sched_stats.as_dict().items()
                if k not in ("queue_age_ms", "tiers")}

    identical_stats = det_stats(t0_sched.stats) == det_stats(ref_sched.stats)

    rate = {tag: results[tag]["stats"].escalation_rate
            for tag, _ in sweep}
    if smoke:
        for tag, _ in sweep:
            assert results[tag]["recompiles"] == 0, (
                f"tier-0 row {tag} recompiled "
                f"{results[tag]['recompiles']} executables after warmup — "
                f"the gate must reuse the warmed pair buckets and decode "
                f"shapes")
        assert rate["esc_0"] == 0.0, (
            f"threshold 0 escalated {rate['esc_0']:.2%} of pairs — "
            f"conf = max(p, 1-p) >= 0.5 must answer everything")
        assert rate["esc_100"] == 1.0, (
            f"threshold 2.0 escalated only {rate['esc_100']:.2%} — "
            f"a threshold > 1 must escalate every pair")
        assert 0.0 < rate["esc_10"] <= 0.3, (
            f"10%-quantile threshold escalated {rate['esc_10']:.2%}")
        assert 0.2 <= rate["esc_50"] <= 0.8, (
            f"50%-quantile threshold escalated {rate['esc_50']:.2%}")
        assert results["esc_10"]["qps"] >= 3.0 * results["esc_100"]["qps"], (
            f"~10% escalation q/s {results['esc_10']['qps']:.2f} is not "
            f">= 3x full reasoning {results['esc_100']['qps']:.2f} — "
            f"tier-0 answers are not skipping the decode")
        assert identical_fields, (
            "tier-0 at threshold 2.0 changed prediction fields vs no "
            "tier-0 head — 100% escalation must be bit-identical")
        assert identical_cache, (
            "tier-0 at threshold 2.0 left different cache contents vs no "
            "tier-0 head")
        assert identical_stats, (
            "tier-0 at threshold 2.0 perturbed deterministic scheduler "
            "stats vs no tier-0 head")

    ref_p = cat(results["esc_100"]["pools"], "p_hat")
    ref_choices = results["esc_100"]["choices"]
    rows = []
    for tag, thr in sweep:
        r = results[tag]
        agree = float(np.mean(r["choices"] == ref_choices))
        p_mae = float(np.mean(np.abs(cat(r["pools"], "p_hat") - ref_p)))
        rows.append({
            "name": f"serve_throughput/tier0_{tag}", "qps": r["qps"],
            "detail": {"threshold": round(thr, 4), "pairs": n_pairs,
                       "tiers": tier_ledger(r["stats"]),
                       "decision_agreement": round(agree, 4),
                       "p_conf_mae": round(p_mae, 4),
                       "recompiles_after_warmup": r["recompiles"],
                       "speedup_vs_full_reasoning": round(
                           r["qps"] / max(results["esc_100"]["qps"], 1e-9),
                           3)}})
    rows.append({
        "name": "serve_throughput/tier0_identity",
        "qps": len(queries) / dt_id,
        "detail": {"threshold": 2.0,
                   "identical_fields": identical_fields,
                   "identical_cache": identical_cache,
                   "identical_stats": identical_stats,
                   "temperature": round(head.temperature, 3)}})
    return rows


def bench_sharded(engine, queries, *, bucket_sizes) -> List[Dict]:
    """Bucketed stream with the estimator placed on the serve mesh."""
    import jax

    from repro.api import RouteRequest
    from repro.launch.mesh import make_serve_mesh
    from repro.serving.scheduler import BucketConfig, MicrobatchScheduler

    n_dev = jax.local_device_count()
    if n_dev < 2:
        return []
    mesh = make_serve_mesh()
    engine.estimator.shard(mesh)
    ticks = _as_ticks(queries, _tick_sizes(len(queries)))
    cfg = BucketConfig(batch_sizes=bucket_sizes)
    run = lambda: list(engine.predict_stream(                  # noqa: E731
        (RouteRequest(t) for t in ticks),
        scheduler=MicrobatchScheduler(cfg), use_cache=False))
    run()                                   # compile sharded executables
    t0 = time.perf_counter()
    run()
    dt = time.perf_counter() - t0
    return [{"name": "serve_throughput/stream_sharded",
             "qps": len(queries) / dt,
             "detail": {"devices": n_dev,
                        "mesh": dict(zip(mesh.axis_names,
                                         mesh.devices.shape,
                                         strict=True))}}]


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------
def _emit(rows: List[Dict], *, smoke: bool) -> None:
    import jax

    from benchmarks._io import write_bench_json
    write_bench_json(BENCH_PATH, {
        "bench": "serve_throughput", "smoke": smoke,
        "unix_time": int(time.time()),
        "devices": jax.local_device_count(), "rows": rows})


def _as_csv_rows(rows: List[Dict]) -> List[Tuple[str, float, str]]:
    out = []
    for r in rows:
        detail = ";".join(f"{k}={v}" for k, v in r["detail"].items())
        out.append((r["name"], 1e6 / max(r["qps"], 1e-9),
                    f"qps={r['qps']:.1f};{detail}"))
    return out


BUCKETS = (1, 2, 4, 8, 16)


def run(bundle) -> List[Tuple[str, float, str]]:
    """benchmarks.run entry point: trained estimator, seen pool."""
    engine = bundle.engine(bundle.seen)
    queries = [bundle.data.queries[int(q)]
               for q in bundle.data.test_qids[:48]]
    rows = bench_stream(engine, queries, bucket_sizes=BUCKETS)
    rows += bench_deadline(engine, queries[:24])
    rows += bench_refill(bundle.engine(bundle.seen), queries,
                         bucket_sizes=BUCKETS)
    rows += bench_paged(bundle.engine(bundle.seen),
                        bundle.engine(bundle.seen, kv_page_size=8),
                        queries, bucket_sizes=BUCKETS)
    rows += bench_chaos(bundle.engine(bundle.seen, kv_page_size=8),
                        queries, bucket_sizes=BUCKETS)
    rows += bench_tier0(bundle.engine(bundle.seen), queries,
                        bucket_sizes=BUCKETS, data=bundle.data,
                        mk=lambda **kw: bundle.engine(bundle.seen, **kw))
    rows += bench_drift(lambda **kw: bundle.engine(bundle.seen, **kw),
                        bundle.data, bucket_sizes=BUCKETS)
    rows += bench_sharded(bundle.engine(bundle.seen), queries,
                          bucket_sizes=BUCKETS)
    _emit(rows, smoke=False)
    return _as_csv_rows(rows)


def _smoke_world():
    """Tiny CI-sized world shared by the smoke engines."""
    from repro.core.fingerprint import FingerprintLibrary, build_anchor_set
    from repro.core.retrieval import AnchorRetriever
    from repro.data.datasets import build_scope_data, stratified_anchors
    from repro.data.worldsim import World

    world = World(seed=0)
    data = build_scope_data(world, n_queries=240, seed=0)
    aset = build_anchor_set(world, stratified_anchors(world, n=60, seed=7))
    library = FingerprintLibrary(aset)
    for m in data.models:
        library.onboard(world, m, seed=3)
    return world, data, library, AnchorRetriever(aset)


def _smoke_engine(world, data, library, retriever, params,
                  max_new_tokens: int = 12, **ekw):
    from repro.api import EngineConfig, ScopeEngine
    from repro.configs.scope_estimator import TINY
    from repro.core.estimator import ReasoningEstimator

    return ScopeEngine.build(EngineConfig(
        estimator=ReasoningEstimator(TINY, params,
                                     max_new_tokens=max_new_tokens),
        retriever=retriever, library=library,
        models_meta={m: world.models[m] for m in data.models}, **ekw))


def _smoke_setup():
    """Tiny untrained world — shapes and scheduling only, CI-sized."""
    import jax

    from repro.configs.scope_estimator import TINY
    from repro.models import model as M

    world, data, library, retriever = _smoke_world()
    params = M.init_params(jax.random.PRNGKey(0), TINY)
    engine = _smoke_engine(world, data, library, retriever, params)
    queries = [data.queries[int(q)] for q in data.test_qids[:10]]
    return engine, queries


def _smoke_trained_setup():
    """Tiny SFT-bootstrapped engine for the refill row.

    A briefly-trained estimator emits EOS at genuinely varying decode
    steps well short of the ``max_new_tokens`` budget (the budget is sized
    for worst-case rationale length, typical generations are much
    shorter), which is the ragged-generation-length regime where mid-batch
    slot refill pays; an untrained one never emits EOS, so every row would
    retire at the same boundary and the refill row would measure nothing.
    """
    import jax

    from repro.configs.scope_estimator import TINY
    from repro.models import model as M
    from repro.training.sft import build_sft_dataset, train_sft

    world, data, library, retriever = _smoke_world()
    ds = build_sft_dataset(data, library, retriever, cot=True,
                           max_examples=800, seed=0)
    params = M.init_params(jax.random.PRNGKey(0), TINY)
    # 130 steps (not 50): enough for most rows to parse well-formed — the
    # drift row's detector only scores well-formed residuals, so a mostly-
    # malformed estimator would starve it of observations
    params, _ = train_sft(params, TINY, ds, steps=130, batch_size=32)

    def mk(**ekw):
        return _smoke_engine(world, data, library, retriever, params,
                             max_new_tokens=16, **ekw)

    engine = mk()
    # same params and pool at a small KV page size — its refill streams
    # must be bit-identical to the whole-retire streams
    paged = mk(kv_page_size=8)
    queries = [data.queries[int(q)] for q in data.test_qids[:16]]
    return engine, paged, queries, data, mk


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny untrained setup (CI gate), asserts "
                         "one-compile-per-bucket + stream==batch")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host CPU devices (before jax loads)")
    ap.add_argument("--repeats", type=int, default=None)
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    if args.smoke:
        engine, queries = _smoke_setup()
        rows = bench_stream(engine, queries, bucket_sizes=(1, 2, 4, 8),
                            repeats=args.repeats or 2, max_tick=3,
                            smoke=True)
        rows += bench_deadline(engine, queries[:6], smoke=True)
        trained, tpaged, tqueries, tdata, tmk = _smoke_trained_setup()
        rows += bench_refill(trained, tqueries, bucket_sizes=(1, 2, 4, 8),
                             repeats=args.repeats or 2, smoke=True)
        rows += bench_paged(trained, tpaged, tqueries,
                            bucket_sizes=(1, 2, 4, 8),
                            repeats=args.repeats or 2, smoke=True)
        rows += bench_chaos(tpaged, tqueries, bucket_sizes=(1, 2, 4, 8),
                            smoke=True)
        rows += bench_tier0(trained, tqueries, bucket_sizes=(1, 2, 4, 8),
                            data=tdata, mk=tmk, distill_steps=60,
                            max_pairs=256, repeats=args.repeats or 2,
                            smoke=True)
        rows += bench_drift(tmk, tdata, bucket_sizes=(1, 2, 4, 8),
                            smoke=True)
        rows += bench_sharded(engine, queries, bucket_sizes=(1, 2, 4, 8))
        _emit(rows, smoke=True)
        print("# smoke asserts passed: zero recompiles after warmup, "
              "overlap+sync streams bit-identical to batch predict, "
              "deadline flush ships partial buckets, refill stream beats "
              "whole-retire q/s at higher slot occupancy with identical "
              "routing decisions, paged KV at page size 8 bit-identical "
              "to whole-retire, chaos stream delivers every pair "
              "exactly once with a consistent fault ledger and the "
              "zero-fault plan bit-identical to no plan, tier-0 gating "
              "answers high-confidence pairs at >= 3x full-reasoning q/s "
              "with 100% escalation bit-identical to no tier-0 head, "
              "drift detector fires within 4 ticks of injected model "
              "drift and the live refresh+hot-swap recovers clean-engine "
              "decisions with zero recompiles")
    else:
        from benchmarks.common import get_bundle
        rows_csv = run(get_bundle())
        for name, us, derived in rows_csv:
            print(f"{name},{us:.2f},{derived}")
        return 0
    print("name,us_per_query,derived")
    for name, us, derived in _as_csv_rows(rows):
        print(f"{name},{us:.2f},{derived}")
    return 0


if __name__ == "__main__":
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    raise SystemExit(main())
