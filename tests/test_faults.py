"""Fault-tolerant serving: deterministic fault injection, bounded
retry/requeue, SLO deadlines, and graceful degradation to retrieval
priors — unit coverage of serving.faults plus stream-level integration
through both serve runtimes."""
import logging

import numpy as np
import pytest

from repro.api import EngineConfig, RouteRequest, ScopeEngine
from repro.api.cache import CachedPrediction, PredictionCache
from repro.core.estimator import (
    FallbackEstimator, ParsedBatch, Prediction, ReasoningEstimator)
from repro.core.status import STATUS_DEGRADED, STATUS_FAILED, STATUS_OK
from repro.data.datasets import build_scope_data
from repro.serving.faults import (
    FaultInjector, FaultPlan, FaultSpec, InjectedFault)
from repro.serving.runtime import ServeRuntime
from repro.serving.scheduler import (
    BucketConfig, Microbatch, MicrobatchScheduler)


# ---------------------------------------------------------------------------
# FaultSpec / FaultPlan / FaultInjector units
# ---------------------------------------------------------------------------
def test_fault_spec_and_plan_validation():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec("gpu_on_fire", 0)
    with pytest.raises(ValueError, match="index"):
        FaultSpec("dispatch", -1)
    with pytest.raises(ValueError, match="duplicate"):
        FaultPlan([FaultSpec("parse", 3), FaultSpec("parse", 3)])
    assert not FaultPlan.none()
    assert FaultPlan([FaultSpec("pool", 0)])


def test_fault_plan_seeded_deterministic():
    rates = {"dispatch": 0.5, "parse": 0.25, "stall": 0.25}
    p1 = FaultPlan.seeded(7, rates=rates, stall_s=3.0)
    p2 = FaultPlan.seeded(7, rates=rates, stall_s=3.0)
    assert p1.specs == p2.specs and p1
    assert FaultPlan.seeded(8, rates=rates, stall_s=3.0).specs != p1.specs
    stalls = [s for s in p1.specs if s.site == "stall"]
    assert stalls and all(s.arg == 3.0 for s in stalls)
    with pytest.raises(ValueError, match="rate"):
        FaultPlan.seeded(0, rates={"dispatch": 1.5})


def _pb(n):
    return ParsedBatch(
        y_hat=np.ones(n, int), len_hat=np.full(n, 9.0),
        well_formed=np.ones(n, bool), p_conf=np.full(n, 0.9),
        pred_tokens=np.full(n, 5), rationale_len=np.full(n, 2))


def test_injector_noop_default_is_inert():
    """No plan (and FaultPlan.none()) must not perturb anything: no spec
    ever fires and corrupt_parse returns the batch object unchanged."""
    for inj in (FaultInjector(), FaultInjector(FaultPlan.none())):
        for _ in range(16):
            assert inj.tick("dispatch") is None
            inj.raise_if("segment")         # never raises
        batch = _pb(3)
        assert inj.corrupt_parse(batch) is batch
        assert inj.fired == 0 and inj.stall_offset == 0.0


def test_injector_fires_planned_events_by_index():
    inj = FaultInjector(FaultPlan([FaultSpec("dispatch", 1),
                                   FaultSpec("stall", 0, arg=2.5)]))
    inj.raise_if("dispatch")                # event 0: clean
    with pytest.raises(InjectedFault, match="dispatch"):
        inj.raise_if("dispatch")            # event 1: fires
    assert inj.tick("stall") is not None
    assert inj.stall_offset == 2.5
    assert inj.fired == 2


def test_corrupt_parse_scrambles_whole_group():
    inj = FaultInjector(FaultPlan([FaultSpec("parse", 1)]))
    first = _pb(3)
    assert inj.corrupt_parse(first) is first        # event 0: untouched
    got = inj.corrupt_parse(_pb(3))                 # event 1: garbage
    assert len(got) == 3 and not got.well_formed.any()
    assert (got.p_conf == 0.5).all() and (got.y_hat == 0).all()
    assert (got.pred_tokens == 5).all()     # tokens were genuinely spent
    assert (got.status == STATUS_OK).all()  # malformed, not degraded


# ---------------------------------------------------------------------------
# Scheduler: requeue / cancel accounting
# ---------------------------------------------------------------------------
def test_scheduler_requeue_and_cancel_accounting():
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(2, 4)),
                                clock=lambda: 0.0)
    sched.submit("a", [5] * 4)
    sched.submit("b", [5] * 4)
    assert sched.flush() and sched.stats.emitted == 2
    sched.requeue("a", [5] * 4)             # retry: not a new submission
    assert sched.stats.submitted == 2 and sched.stats.requeued == 1
    assert sched.cancel("a") == [5] * 4     # queued row: removed
    assert sched.cancel("a") is None        # exactly-once
    assert sched.cancel("zzz") is None      # unknown tag
    assert len(sched) == 0


# ---------------------------------------------------------------------------
# FallbackEstimator: degraded answers from retrieval priors
# ---------------------------------------------------------------------------
def test_fallback_estimator_prior_predictions(world, library):
    model = next(m.name for m in world.pool if m.seen)
    fp = library.get(model)
    sims = np.array([[0.9, 0.5, 0.1], [0.0, 0.0, 0.0]])
    idx = np.array([[0, 1, 2], [3, 4, 5]])
    out = FallbackEstimator(library).predict_pairs(sims, idx,
                                                   [model, model])
    assert (out.status == STATUS_DEGRADED).all()
    assert out.well_formed.all()            # priced at the predicted len,
    assert (out.pred_tokens == 0).all()     # zero decode tokens spent
    assert ((out.p_conf >= 0.0) & (out.p_conf <= 1.0)).all()
    np.testing.assert_array_equal(out.y_hat,
                                  (out.p_conf >= 0.5).astype(int))
    w = sims[0] / sims[0].sum()             # similarity-weighted priors
    np.testing.assert_allclose(out.p_conf[0],
                               w @ np.asarray(fp.y, float)[idx[0]])
    np.testing.assert_allclose(out.len_hat[0],
                               w @ np.asarray(fp.tokens, float)[idx[0]])
    # zero-similarity rows fall back to uniform anchor weighting
    np.testing.assert_allclose(out.p_conf[1],
                               np.asarray(fp.y, float)[idx[1]].mean())


def test_fallback_failed_pairs_shape():
    out = FallbackEstimator.failed_pairs(2)
    assert (out.status == STATUS_FAILED).all()
    assert not out.well_formed.any()        # pessimistic-fallback pricing
    assert (out.p_conf == 0.0).all() and (out.pred_tokens == 0).all()


# ---------------------------------------------------------------------------
# Cache: the tier-0/tier-1 degraded-overwrite scheme
# ---------------------------------------------------------------------------
def test_cache_degraded_tier_overwrite_rules():
    cache = PredictionCache()
    ok = CachedPrediction(1, 9.0, True, 0.8, 5, 7, status=STATUS_OK)
    deg = CachedPrediction(0, 3.0, True, 0.4, 0, 7,
                           status=STATUS_DEGRADED)
    cache.put(1, "m", "v", ok)
    cache.put(1, "m", "v", deg)             # degraded never clobbers OK
    assert cache._store[(1, "m", "v")].status == STATUS_OK
    cache.put(2, "m", "v", deg)
    deg2 = CachedPrediction(1, 4.0, True, 0.6, 0, 7,
                            status=STATUS_DEGRADED)
    cache.put(2, "m", "v", deg2)            # degraded refresh is allowed
    assert cache._store[(2, "m", "v")].p_conf == 0.6
    cache.put_many([(2, "m", "v")], [ok])   # a late real decode heals
    assert cache._store[(2, "m", "v")].status == STATUS_OK
    cache.put_many([(2, "m", "v")], [deg])  # and stays healed
    assert cache._store[(2, "m", "v")].status == STATUS_OK


# ---------------------------------------------------------------------------
# ServeRuntime: failure routing, close(), context manager
# ---------------------------------------------------------------------------
class _H:
    def __init__(self, name, ready=False, bad=False):
        self.name, self._ready, self._bad = name, ready, bad

    def is_ready(self):
        return self._ready

    def parse(self):
        if self._bad:
            raise ValueError("garbage result")
        return self.name


def _mb(name):
    return Microbatch(np.zeros((1, 4), np.int32), [name],
                      np.full((1,), 4, np.int32), (1, 4))


def test_serve_runtime_routes_dispatch_and_parse_failures():
    parsed, failed = [], []

    def dispatch(mb):
        if mb.tags[0] == "boom":
            raise RuntimeError("dispatch died")
        return _H(mb.tags[0], bad=mb.tags[0] == "bad")

    rt = ServeRuntime(dispatch, on_parsed=lambda mb, r: parsed.append(r),
                      max_pending=1,
                      on_failed=lambda mb, exc: failed.append(mb.tags[0]))
    rt.dispatch([_mb("boom"), _mb("a"), _mb("bad")])
    rt.finish()
    assert parsed == ["a"] and failed == ["boom", "bad"]
    assert rt.stats.failed == 2 and len(rt) == 0
    # without on_failed the exception stays loud (pre-fault behavior)
    rt2 = ServeRuntime(dispatch, on_parsed=lambda mb, r: None)
    with pytest.raises(RuntimeError, match="dispatch died"):
        rt2.dispatch([_mb("boom")])


def test_serve_runtime_close_and_context_manager():
    parsed = []

    def mk():
        return ServeRuntime(lambda mb: _H(mb.tags[0]),
                            on_parsed=lambda mb, r: parsed.append(r),
                            max_pending=4)

    with mk() as rt:                        # clean exit drains
        rt.dispatch([_mb("a"), _mb("b")])
        assert len(rt) == 2
    assert parsed == ["a", "b"] and len(rt) == 0

    parsed.clear()
    with pytest.raises(RuntimeError, match="stream died"):
        with mk() as rt:                    # error exit aborts, no parse
            rt.dispatch([_mb("c")])
            raise RuntimeError("stream died")
    assert parsed == [] and len(rt) == 0

    rt = mk()
    rt.dispatch([_mb("d")])
    rt.close(drain=False)                   # explicit abort
    assert parsed == [] and len(rt) == 0


# ---------------------------------------------------------------------------
# Stream integration: faults through the real engine
# ---------------------------------------------------------------------------
@pytest.fixture()
def chaos_engine(tiny_trained, world, retriever, library):
    cfg, params, _ = tiny_trained
    data = build_scope_data(world, n_queries=160, seed=9)

    def mk(max_new_tokens=6, **kw):
        return ScopeEngine.build(EngineConfig(
            estimator=ReasoningEstimator(cfg, params,
                                         max_new_tokens=max_new_tokens),
            retriever=retriever, library=library,
            models_meta={m: world.models[m] for m in data.models}, **kw))
    return mk, data


def _run(mk, data, n=6, ticks=2, *, use_cache=False, refill=False,
         segment_len=4, bucket_sizes=(1, 2, 4, 8), **cfg_kw):
    engine = mk(**cfg_kw)
    qs = [data.queries[int(q)] for q in data.test_qids[:n]]
    reqs = [RouteRequest([qs[i] for i in c])
            for c in np.array_split(np.arange(n), ticks)]
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=bucket_sizes))
    pools = list(engine.predict_stream(
        iter(reqs), scheduler=sched, use_cache=use_cache, refill=refill,
        segment_len=segment_len if refill else None))
    return engine, sched, pools


def _cat(pools, field):
    return np.concatenate([getattr(p, field) for p in pools], axis=0)


def test_dispatch_fault_retries_to_fault_free_parity(chaos_engine):
    """A failed dispatch requeues its rows; the retried decode lands the
    stream on the exact fault-free answers (token-derived fields bit-equal,
    confidences to ulp — retried rows ride different-shaped buckets)."""
    mk, data = chaos_engine
    _, _, ref = _run(mk, data)
    _, sched, got = _run(mk, data, max_retries=2,
                         fault_plan=FaultPlan([FaultSpec("dispatch", 0)]))
    st = sched.stats
    assert st.injected_faults == 1 and st.retries == 1
    assert st.unexpected_failures == 0 and st.first_failure == ""
    assert st.requeued > 0 and st.quarantined == 0
    assert st.deadline_expired == 0 and st.degraded == 0
    assert (_cat(got, "status") == STATUS_OK).all()
    for f in ("y_hat", "len_hat", "well_formed", "cost_hat"):
        np.testing.assert_array_equal(_cat(got, f), _cat(ref, f),
                                      err_msg=f)
    np.testing.assert_allclose(_cat(got, "p_hat"), _cat(ref, "p_hat"),
                               atol=1e-6, rtol=1e-6)


class _FlakyEstimator:
    """Raises a failure no FaultPlan injected on its first call, then
    answers every prompt the same well-formed way."""

    def __init__(self):
        self.calls = 0

    def predict(self, prompts, rng=None, **kw):
        self.calls += 1
        if self.calls == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")
        return [Prediction(y_hat=1, len_hat=64.0, well_formed=True,
                           p_conf=0.75, pred_tokens=6, rationale_len=4)
                for _ in prompts]


def test_uninjected_dispatch_error_is_counted_and_kept(world, retriever,
                                                       library, caplog):
    """A real (non-injected) dispatch error is retried like an injected
    one, but the faults ledger counts it and keeps its message, and it is
    logged once — it can never pass silently as a degrade."""
    data = build_scope_data(world, n_queries=40, seed=9)
    engine = ScopeEngine.build(EngineConfig(
        estimator=_FlakyEstimator(), retriever=retriever, library=library,
        models_meta={m: world.models[m] for m in data.models},
        max_retries=1))
    qs = [data.queries[int(q)] for q in data.test_qids[:2]]
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
    with caplog.at_level(logging.ERROR, logger="repro.api.engine"):
        pools = list(engine.predict_stream([RouteRequest(qs)],
                                           scheduler=sched))
    st = sched.stats
    assert st.injected_faults == 0 and st.retries == 1
    assert st.unexpected_failures == 1
    assert st.first_failure == "RuntimeError: RESOURCE_EXHAUSTED: out of HBM"
    faults = st.as_dict()["faults"]
    assert faults["unexpected"] == 1
    assert faults["first_failure"] == st.first_failure
    assert st.quarantined == 0 and st.degraded == 0
    assert (_cat(pools, "status") == STATUS_OK).all()
    logged = [r for r in caplog.records if "not injected" in r.getMessage()]
    assert len(logged) == 1 and logged[0].exc_info is not None


def test_quarantine_answers_from_retrieval_priors(chaos_engine):
    """max_retries=0: the failed microbatch's pairs quarantine and come
    back DEGRADED from the FallbackEstimator — well-formed, zero decode
    overhead — and the degradation ledger balances."""
    mk, data = chaos_engine
    _, sched, got = _run(mk, data, max_retries=0,
                         fault_plan=FaultPlan([FaultSpec("dispatch", 0)]))
    st = sched.stats
    assert st.retries == 1 and st.requeued == 0
    assert st.quarantined > 0 and st.degraded == st.quarantined
    assert st.failed_pairs == 0 and st.deadline_expired == 0
    status = _cat(got, "status")
    n_deg = int((status == STATUS_DEGRADED).sum())
    assert n_deg == st.degraded + st.failed_pairs \
        == st.quarantined + st.deadline_expired
    assert not (status == STATUS_FAILED).any()
    deg = status == STATUS_DEGRADED
    assert _cat(got, "well_formed")[deg].all()
    assert (_cat(got, "pred_overhead")[deg] == 0).all()
    assert any(p.degraded_fraction > 0.0 for p in got)


def test_no_degrade_marks_pairs_failed(chaos_engine):
    """degrade=False: quarantined pairs are FAILED outright — malformed-
    estimate pricing instead of retrieval priors."""
    mk, data = chaos_engine
    _, sched, got = _run(mk, data, max_retries=0, degrade=False,
                         fault_plan=FaultPlan([FaultSpec("dispatch", 0)]))
    st = sched.stats
    assert st.quarantined > 0 and st.failed_pairs == st.quarantined
    assert st.degraded == 0
    status = _cat(got, "status")
    bad = status == STATUS_FAILED
    assert int(bad.sum()) == st.failed_pairs
    assert not (status == STATUS_DEGRADED).any()
    assert not _cat(got, "well_formed")[bad].any()


def test_deadline_expiry_degrades_and_late_parses_heal(chaos_engine):
    """An injected clock stall expires pairs past their SLO: each answers
    DEGRADED immediately.  A pair expiring while *queued* is cancelled
    outright — its decode never runs, so its prior-based cache entry
    (zero decode tokens) remains; a pair expiring *in flight* keeps
    decoding, and its late parse heals the entry to a full OK prediction.
    The single 8-wide bucket guarantees a queued remainder."""
    mk, data = chaos_engine
    engine, sched, got = _run(
        mk, data, use_cache=True, max_retries=2, deadline_ms=60_000.0,
        bucket_sizes=(8,),
        fault_plan=FaultPlan([FaultSpec("stall", 0, arg=1e6)]))
    st = sched.stats
    assert st.injected_faults == 1
    assert st.deadline_expired > 0 and st.degraded == st.deadline_expired
    assert st.quarantined == 0 and st.failed_pairs == 0
    status = _cat(got, "status")
    n_deg = int((status == STATUS_DEGRADED).sum())
    assert n_deg == st.degraded and not (status == STATUS_FAILED).any()
    entries = list(engine.cache._store.values())
    assert len(entries) == status.size
    stale = [e for e in entries if e.status != STATUS_OK]
    # cancelled-from-queue pairs: degraded entry, no decode ever ran
    assert 0 < len(stale) <= st.deadline_expired
    assert all(e.status == STATUS_DEGRADED and e.pred_tokens == 0
               for e in stale)
    # every pair whose decode ran has a full OK entry — never-expired
    # pairs directly, in-flight-expired pairs via the late-parse heal
    assert len(entries) - len(stale) >= status.size - st.deadline_expired


def test_parse_garbage_is_malformed_not_retried(chaos_engine):
    """Injected parse garbage flows through the malformed-estimate
    machinery (tokens were spent, the answer exists but is unusable): no
    retry, no degradation, just well_formed=False rows.  A 10-token
    budget lets the reference parse cleanly so the scrambled group is
    visible against it."""
    mk, data = chaos_engine
    _, _, ref = _run(mk, data, max_new_tokens=10)
    _, sched, got = _run(mk, data, max_new_tokens=10, max_retries=2,
                         fault_plan=FaultPlan([FaultSpec("parse", 0)]))
    st = sched.stats
    assert st.injected_faults == 1
    assert st.retries == 0 and st.quarantined == 0
    assert st.deadline_expired == 0 and st.degraded == 0
    assert (_cat(got, "status") == STATUS_OK).all()
    n_bad = int((~_cat(got, "well_formed")).sum())
    assert n_bad > int((~_cat(ref, "well_formed")).sum())


def test_refill_segment_and_pool_faults_recover(chaos_engine):
    """Refill path: a segment teardown requeues the whole live state and a
    KV-pool exhaustion fails a single row; both retry to the exact
    fault-free answers and the kv_exhausted_rows counter records the
    row-level failure."""
    mk, data = chaos_engine
    paged = {"kv_page_size": 8}
    _, _, ref = _run(mk, data, refill=True, **paged)
    plan = FaultPlan([FaultSpec("segment", 1), FaultSpec("pool", 2)])
    _, sched, got = _run(mk, data, refill=True, max_retries=2,
                         fault_plan=plan, **paged)
    st = sched.stats
    assert st.injected_faults == 2
    assert st.kv_exhausted_rows == 1
    assert st.retries == 2 and st.requeued >= 2
    assert st.quarantined == 0
    assert (_cat(got, "status") == STATUS_OK).all()
    for f in ("y_hat", "len_hat", "well_formed", "cost_hat"):
        np.testing.assert_array_equal(_cat(got, f), _cat(ref, f),
                                      err_msg=f)
    np.testing.assert_allclose(_cat(got, "p_hat"), _cat(ref, "p_hat"),
                               atol=1e-6, rtol=1e-6)


def test_inflight_dedup_resolves_and_clears_across_ticks(chaos_engine):
    """Regression: the in-flight dedup map must drop a key once resolved.
    Duplicate pairs share one decode within a tick; with the cache
    evicting immediately (capacity=0) the same key is re-submitted in a
    later tick — a stale in-flight entry would strand it forever.  Runs
    both the retry and the quarantine resolution paths."""
    mk, data = chaos_engine
    qs = [data.queries[int(q)] for q in data.test_qids[:3]]
    plan = FaultPlan([FaultSpec("dispatch", 0)])
    for retries in (1, 0):
        engine = mk(fault_plan=plan, max_retries=retries)
        engine.cache.capacity = 0           # evict on every put
        sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
        reqs = [RouteRequest([qs[0], qs[0], qs[1]]),
                RouteRequest([qs[0], qs[1], qs[2], qs[2]])]
        pools = list(engine.predict_stream(iter(reqs), scheduler=sched,
                                           use_cache=True))
        assert len(pools) == 2
        # duplicate queries in one request share one resolution
        np.testing.assert_array_equal(pools[0].y_hat[0], pools[0].y_hat[1])
        np.testing.assert_array_equal(pools[1].y_hat[2], pools[1].y_hat[3])
        status = _cat(pools, "status")
        if retries:
            assert (status == STATUS_OK).all()
            assert sched.stats.quarantined == 0 and sched.stats.requeued > 0
        else:
            assert sched.stats.quarantined > 0
            assert (pools[0].status == STATUS_DEGRADED).any()
            assert (pools[1].status == STATUS_OK).all()
        assert len(engine.cache._store) == 0    # capacity 0 really evicts
