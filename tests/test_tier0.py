"""Two-tier routing: tier-0 pre-router head, confidence-gated escalation
in the engine, cache tier rules, the scheduler tier ledger, and the
quarantine fallback ladder (tier-0 answer before retrieval prior)."""
import numpy as np
import pytest

from repro.api import EngineConfig, RouteRequest, ScopeEngine
from repro.api.cache import CachedPrediction, PredictionCache
from repro.core.contract import assert_cross_shape
from repro.core.estimator import ReasoningEstimator
from repro.core.status import STATUS_DEGRADED, STATUS_FAILED, STATUS_OK
from repro.data.datasets import build_scope_data
from repro.models import tier0 as T0
from repro.serving.faults import FaultPlan, FaultSpec
from repro.serving.scheduler import BucketConfig, MicrobatchScheduler
from repro.training.tier0 import (
    build_tier0_dataset, fit_temperature, train_tier0)


# ---------------------------------------------------------------------------
# Cache tier rules: rank = (status == OK, tier)
# ---------------------------------------------------------------------------
def _pred(tier, status=STATUS_OK, p=0.7):
    return CachedPrediction(y_hat=1, len_hat=64.0, well_formed=True,
                            p_conf=p, pred_tokens=0, prompt_tokens=49,
                            status=status, tier=tier)


def test_cache_tier1_overwrites_tier0_never_reverse():
    cache = PredictionCache()
    key = (1, "m", "v0")
    cache.put(*key, _pred(0, p=0.6))
    cache.put(*key, _pred(1, p=0.9))            # escalated decode heals
    assert cache.get(*key).tier == 1 and cache.get(*key).p_conf == 0.9
    cache.put(*key, _pred(0, p=0.1))            # tier-0 never clobbers
    assert cache.get(*key).tier == 1 and cache.get(*key).p_conf == 0.9
    cache.put(*key, _pred(1, p=0.4))            # same rank: refresh
    assert cache.get(*key).p_conf == 0.4


def test_cache_version_bump_invalidates_both_tiers():
    cache = PredictionCache()
    cache.put(1, "m", "v0", _pred(0))
    cache.put(2, "m", "v0", _pred(1))
    assert cache.get(1, "m", "v1") is None
    assert cache.get(2, "m", "v1") is None
    # the old version's entries are untouched, just unreachable by v1 keys
    assert cache.get(1, "m", "v0").tier == 0


def test_cache_degraded_interaction_with_tiers():
    cache = PredictionCache()
    key = (1, "m", "v0")
    # a tier-0 OK answer resists degraded writes of any tier
    cache.put(*key, _pred(0))
    cache.put(*key, _pred(1, status=STATUS_DEGRADED))
    assert cache.get(*key).status == STATUS_OK and cache.get(*key).tier == 0
    cache.put(*key, _pred(1, status=STATUS_FAILED))
    assert cache.get(*key).status == STATUS_OK
    # OK of either tier heals a degraded entry
    cache.put(1, "n", "v0", _pred(0, status=STATUS_DEGRADED))
    cache.put(1, "n", "v0", _pred(0, status=STATUS_OK))
    assert cache.get(1, "n", "v0").status == STATUS_OK
    cache.put(2, "n", "v0", _pred(1, status=STATUS_DEGRADED))
    cache.put(2, "n", "v0", _pred(0, status=STATUS_OK, p=0.8))
    got = cache.get(2, "n", "v0")
    assert got.status == STATUS_OK and got.tier == 0 and got.p_conf == 0.8
    # among degraded entries, a tier-1 (prior) entry resists a tier-0 one
    cache.put(3, "n", "v0", _pred(1, status=STATUS_DEGRADED, p=0.3))
    cache.put(3, "n", "v0", _pred(0, status=STATUS_DEGRADED, p=0.2))
    assert cache.get(3, "n", "v0").p_conf == 0.3


def test_cache_default_tier_is_one_and_legacy_rule_preserved():
    """Entries written without an explicit tier behave exactly like PR 7:
    OK overwrites anything, non-OK never clobbers OK."""
    cache = PredictionCache()
    key = (9, "m", "v0")
    cache.put(*key, CachedPrediction(1, 8.0, True, 0.9, 5, 49,
                                     status=STATUS_DEGRADED))
    cache.put(*key, CachedPrediction(0, 9.0, True, 0.6, 5, 49))
    assert cache.get(*key).status == STATUS_OK
    cache.put(*key, CachedPrediction(1, 8.0, True, 0.9, 5, 49,
                                     status=STATUS_DEGRADED))
    assert cache.get(*key).status == STATUS_OK and cache.get(*key).tier == 1


# ---------------------------------------------------------------------------
# Head units: shapes, determinism, bucket padding, compile counts
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def head():
    import jax
    return T0.Tier0Head(T0.init_tier0(jax.random.PRNGKey(3)))


def _rand_pairs(n, k=5, seed=0):
    r = np.random.default_rng(seed)
    return (r.normal(size=(n, T0.QUERY_FEATS)).astype(np.float32),
            r.normal(size=(n, k, T0.ANCHOR_FEATS)).astype(np.float32),
            r.normal(size=(n, T0.MODEL_FEATS)).astype(np.float32),
            r.integers(0, T0.N_MODEL_SLOTS, size=n).astype(np.int32))


def test_pair_bucket_grid():
    assert T0.pair_bucket(1) == T0.PAIR_BUCKETS[0]
    assert T0.pair_bucket(16) == 16
    assert T0.pair_bucket(17) == 64
    top = T0.PAIR_BUCKETS[-1]
    assert T0.pair_bucket(top + 1) == 2 * top


def test_head_deterministic_and_pad_invariant(head):
    qf, af, mf, mid = _rand_pairs(7)
    a = head.predict_pairs(qf, af, mf, mid)
    b = head.predict_pairs(qf, af, mf, mid)
    np.testing.assert_array_equal(a.p, b.p)
    assert len(a) == 7
    assert (a.conf >= 0.5).all() and (a.conf <= 1.0).all()
    np.testing.assert_array_equal(a.y_hat, (a.p >= 0.5).astype(int))
    # the same rows padded into a larger pair bucket: another shape, so
    # the cross-shape contract — identical decisions, floats to ulp
    qf2, af2, mf2, mid2 = _rand_pairs(40, seed=1)
    qf2[:7], af2[:7], mf2[:7], mid2[:7] = qf, af, mf, mid
    c = head.predict_pairs(qf2, af2, mf2, mid2)
    assert_cross_shape(c.p[:7], a.p, err_msg="p")
    assert_cross_shape(c.y_hat[:7], a.y_hat, err_msg="y_hat")


def test_head_one_compile_per_bucket(head):
    before = int(T0.COMPILE_COUNTS["tier0"])
    for n in (3, 9, 14):                    # all pad to bucket 16
        head.predict_pairs(*_rand_pairs(n, seed=n))
    mid_count = int(T0.COMPILE_COUNTS["tier0"])
    assert mid_count - before <= 1          # 16-bucket may be warm already
    for n in (3, 9, 14):
        head.predict_pairs(*_rand_pairs(n, seed=100 + n))
    assert int(T0.COMPILE_COUNTS["tier0"]) == mid_count


def test_head_empty_batch_and_temperature_validation(head):
    out = head.predict_pairs(np.zeros((0, T0.QUERY_FEATS), np.float32),
                             np.zeros((0, 5, T0.ANCHOR_FEATS), np.float32),
                             np.zeros((0, T0.MODEL_FEATS), np.float32),
                             np.zeros(0, np.int32))
    assert len(out) == 0
    with pytest.raises(ValueError, match="temperature"):
        head.with_temperature(0.0)
    # temperature flattens the calibrated probability toward chance
    qf, af, mf, mid = _rand_pairs(8, seed=5)
    sharp = head.predict_pairs(qf, af, mf, mid)
    flat = head.with_temperature(50.0).predict_pairs(qf, af, mf, mid)
    assert (flat.conf <= sharp.conf + 1e-12).all()
    np.testing.assert_array_equal(flat.y_hat, sharp.y_hat)  # sign-preserving


def test_pair_features_shapes_and_unseen_slot(world, library, scope_data):
    m_seen = next(m for m in world.pool if m.seen)
    q = scope_data.queries[0]
    sims = np.array([0.9, 0.5, 0.3, 0.2, 0.1])
    idx = np.arange(5)
    qf, af, mf, mid = T0.pair_features(
        m_seen, 2, library.anchor_set, library.get(m_seen.name),
        sims, idx, q)
    assert qf.shape == (T0.QUERY_FEATS,) and af.shape == (5, T0.ANCHOR_FEATS)
    assert mf.shape == (T0.MODEL_FEATS,) and 0 <= mid < T0.N_MODEL_SLOTS - 1
    import dataclasses
    unseen = dataclasses.replace(m_seen, seen=False)
    _, _, _, mid_u = T0.pair_features(
        unseen, 2, library.anchor_set, library.get(m_seen.name),
        sims, idx, q)
    assert mid_u == T0.N_MODEL_SLOTS - 1    # shared UNK slot


def test_fit_temperature_recovers_scale():
    r = np.random.default_rng(0)
    logit = r.normal(scale=4.0, size=4000)
    q = 1.0 / (1.0 + np.exp(-logit / 2.0))  # true temperature 2.0
    t = fit_temperature(logit, q)
    assert 1.5 < t < 2.7
    assert fit_temperature(np.zeros(0), np.zeros(0)) == 1.0


# ---------------------------------------------------------------------------
# Distillation + engine integration (shared trained setup)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tier0_setup(tiny_trained, world, retriever, library):
    cfg, params, _ = tiny_trained
    data = build_scope_data(world, n_queries=160, seed=9)
    est = ReasoningEstimator(cfg, params, max_new_tokens=6)
    ds = build_tier0_dataset(data, library, retriever, est,
                             max_pairs=240, seed=0)
    head, report = train_tier0(ds, steps=60, batch_size=128, seed=0)

    def mk(tier0=None, threshold=0.9, **kw):
        return ScopeEngine.build(EngineConfig(
            estimator=ReasoningEstimator(cfg, params, max_new_tokens=6),
            retriever=retriever, library=library,
            models_meta={m: world.models[m] for m in data.models},
            tier0=tier0, escalation_threshold=threshold, **kw))
    return mk, data, head, report


def test_distillation_trains_and_calibrates(tier0_setup):
    _, _, head, report = tier0_setup
    assert np.isfinite(report.losses).all()
    assert np.mean(report.losses[-10:]) < np.mean(report.losses[:10])
    assert report.temperature > 0.0 and report.n_val > 0
    assert head.temperature == report.temperature


def _pool_fields(pool):
    return {f: getattr(pool, f) for f in
            ("p_hat", "y_hat", "len_hat", "cost_hat", "well_formed",
             "pred_overhead", "sims", "idx")}


def test_threshold_above_one_is_bit_identical_to_no_tier0(tier0_setup):
    """100% escalation: same decisions, same cache contents, same stats —
    the gate runs but answers nothing, so the decode path sees exactly
    the traffic it would without a head."""
    mk, data, head, _ = tier0_setup
    queries = [data.queries[int(q)] for q in data.test_qids[:6]]
    ref_eng = mk(tier0=None)
    got_eng = mk(tier0=head, threshold=2.0)
    ref = ref_eng.predict(RouteRequest(queries))
    got = got_eng.predict(RouteRequest(queries))
    for f, v in _pool_fields(ref).items():
        np.testing.assert_array_equal(getattr(got, f), v, err_msg=f)
    assert got.cache_hits == ref.cache_hits
    assert got.cache_misses == ref.cache_misses
    assert got.tier0_answered == 0
    assert got.escalated == got.cache_misses > 0
    assert got_eng.cache._store == ref_eng.cache._store  # incl. tiers


def test_threshold_zero_answers_everything_no_scheduler_entry(tier0_setup):
    """0% escalation: every missing pair is answered by the head — nothing
    is ever submitted to the scheduler, so nothing can reach the in-flight
    dedup map (the leak class PR 7 fixed for dispatch faults)."""
    mk, data, head, _ = tier0_setup
    engine = mk(tier0=head, threshold=0.0)
    queries = [data.queries[int(q)] for q in data.test_qids[:6]]
    reqs = [RouteRequest(queries[:3]), RouteRequest(queries[3:])]
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
    pools = list(engine.predict_stream(iter(reqs), scheduler=sched))
    st = sched.stats
    n_pairs = 6 * len(data.models)
    assert st.submitted == 0 and st.emitted == 0 and st.microbatches == 0
    assert st.tier0_answered == n_pairs and st.escalated == 0
    assert st.escalation_rate == 0.0
    assert st.tier0_decode_tokens_saved == n_pairs * 6
    for pool in pools:
        assert (pool.status == STATUS_OK).all()
        assert pool.well_formed.all()
        assert (pool.pred_overhead == 0).all()      # no decode tokens
        assert ((pool.p_hat >= 0.0) & (pool.p_hat <= 1.0)).all()
    # every cache entry written by the gate carries tier 0
    assert len(engine.cache) == n_pairs
    assert all(e.tier == 0 and e.status == STATUS_OK
               for e in engine.cache._store.values())
    d = st.as_dict()["tiers"]
    assert d["tier0_answered"] == n_pairs and d["escalation_rate"] == 0.0


def test_partial_threshold_splits_traffic_exactly(tier0_setup):
    """A mid-sweep threshold: answered + escalated == all missing pairs,
    and only the escalated ones are submitted to the scheduler."""
    mk, data, head, _ = tier0_setup
    queries = [data.queries[int(q)] for q in data.test_qids[:6]]
    n_pairs = 6 * len(data.models)
    # pick a threshold at the median confidence so both sides are non-empty
    probe = mk(tier0=head, threshold=0.0)       # head answers everything:
    pool = probe.predict(RouteRequest(queries), use_cache=False)
    conf = np.maximum(pool.p_hat, 1.0 - pool.p_hat)  # p_hat is the head's p
    engine = mk(tier0=head, threshold=float(np.median(conf)))
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
    pools = list(engine.predict_stream(
        iter([RouteRequest(queries)]), scheduler=sched))
    st = sched.stats
    assert st.tier0_answered + st.escalated == n_pairs
    assert st.tier0_answered > 0 and st.escalated > 0
    assert st.submitted == st.escalated
    assert pools[0].tier0_answered == st.tier0_answered
    tiers = {e.tier for e in engine.cache._store.values()}
    assert tiers == {0, 1}
    assert 0.0 < st.escalation_rate < 1.0


def test_quarantined_escalation_falls_back_to_tier0_answer(tier0_setup):
    """An escalated pair whose decode quarantines is answered from its
    stashed tier-0 row — the head's calibrated estimate, not the
    retrieval prior — as DEGRADED with zero decode overhead."""
    mk, data, head, _ = tier0_setup
    queries = [data.queries[int(q)] for q in data.test_qids[:4]]
    # reference: what the head alone says for every pair
    t0_pool = mk(tier0=head, threshold=0.0).predict(
        RouteRequest(queries), use_cache=False)
    engine = mk(tier0=head, threshold=2.0, max_retries=0,
                fault_plan=FaultPlan([FaultSpec("dispatch", 0)]))
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
    pools = list(engine.predict_stream(
        iter([RouteRequest(queries)]), scheduler=sched, use_cache=False))
    st = sched.stats
    assert st.quarantined > 0
    assert st.tier0_fallbacks == st.quarantined == st.degraded
    status = pools[0].status
    deg = status == STATUS_DEGRADED
    assert int(deg.sum()) == st.quarantined
    np.testing.assert_allclose(pools[0].p_hat[deg], t0_pool.p_hat[deg],
                               rtol=0, atol=0)
    np.testing.assert_array_equal(pools[0].len_hat[deg],
                                  t0_pool.len_hat[deg])
    assert pools[0].well_formed[deg].all()
    assert (pools[0].pred_overhead[deg] == 0).all()
    # degradation ledger stays balanced (PR 7 invariant)
    assert st.degraded + st.failed_pairs == \
        st.quarantined + st.deadline_expired


def test_degrade_cache_entry_from_tier0_is_tier0_and_healable(tier0_setup):
    """With the cache on, a quarantined escalation writes a DEGRADED
    tier-0 entry; a later real decode (OK tier-1) heals it."""
    mk, data, head, _ = tier0_setup
    queries = [data.queries[int(q)] for q in data.test_qids[:2]]
    engine = mk(tier0=head, threshold=2.0, max_retries=0,
                fault_plan=FaultPlan([FaultSpec("dispatch", 0)]))
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
    list(engine.predict_stream(iter([RouteRequest(queries)]),
                               scheduler=sched))
    assert sched.stats.tier0_fallbacks > 0
    deg_entries = {k: e for k, e in engine.cache._store.items()
                   if e.status == STATUS_DEGRADED}
    assert deg_entries and all(e.tier == 0 for e in deg_entries.values())
    # clean second pass over the same queries: misses are the degraded
    # keys only... none (DEGRADED entries are hits).  Force the heal by
    # writing through put_many as _stream_fill would.
    key = next(iter(deg_entries))
    engine.cache.put_many([key], [CachedPrediction(
        1, 12.0, True, 0.8, 6, 49, status=STATUS_OK, tier=1)])
    healed = engine.cache._store[key]
    assert healed.status == STATUS_OK and healed.tier == 1


def test_stale_tier0_stash_refused_after_hot_swap(tier0_setup):
    """Regression: ``degrade()`` must refuse a tier-0 fallback row stashed
    under a pre-swap estimator version — the old head's calibration
    belongs to the old params — and fall to the retrieval-prior rung
    (still answered DEGRADED exactly once, just without the stash)."""
    from repro.api.engine import _StreamControl, _StreamEntry
    mk, data, head, _ = tier0_setup
    queries = [data.queries[int(q)] for q in data.test_qids[:1]]

    def degrade_one(engine, *, swap):
        st = engine._prepare(RouteRequest(queries), use_cache=False)
        assert st.t0_rows      # threshold 2.0: every pair escalates, stashed
        entry = _StreamEntry(st)
        sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
        inflight = {}
        control = _StreamControl(engine, sched, inflight, use_cache=False)
        engine._submit_misses(st, entry, sched, inflight, False, 0, control)
        key = next(iter(control.t0_rows))
        assert control.t0_rows[key][0] == "v0"      # stamped at submit time
        if swap:
            engine.hot_swap(engine.estimator, "v0+swap")
        control.degrade(key)
        assert entry.remaining == len(st.prompts) - 1   # exactly one filled
        assert entry.status[0] == STATUS_DEGRADED
        return sched.stats

    # matching version: the stash answers on the tier-0 fallback rung
    stats = degrade_one(mk(tier0=head, threshold=2.0), swap=False)
    assert stats.degraded == 1 and stats.tier0_fallbacks == 1
    # post-swap: the stale stash is refused, the retrieval prior answers
    stats = degrade_one(mk(tier0=head, threshold=2.0), swap=True)
    assert stats.degraded == 1 and stats.tier0_fallbacks == 0


# ---------------------------------------------------------------------------
# Static enforcement + ledger surfacing
# ---------------------------------------------------------------------------
def test_tier0_registered_as_hot_path_executable():
    from repro.analysis.jaxpr_pass import registered
    from repro.analysis.manifest import is_hot_path
    assert "tier0_forward" in registered()
    assert is_hot_path("src/repro/models/tier0.py")


def test_tier0_compile_counter_surfaced():
    from repro.serving.scheduler import decode_compile_counts
    counts = decode_compile_counts()
    assert "tier0" in counts and counts["tier0"] >= 0
