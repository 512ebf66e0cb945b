"""Streaming serve subsystem: bucketed microbatch scheduler, stream-vs-batch
predict parity, fixed-executable reuse, sharded multi-device serving, and
the ``_pad_caches`` seq-axis contract."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EngineConfig, FixedAlphaPolicy, RouteRequest, ScopeEngine
from repro.core.contract import assert_cross_shape
from repro.core.estimator import Prediction
from repro.data.datasets import build_scope_data
from repro.serving.sampler import _pad_caches
from repro.serving.scheduler import (
    BucketConfig, MicrobatchScheduler, decode_compile_counts)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# BucketConfig / MicrobatchScheduler unit behavior
# ---------------------------------------------------------------------------
def test_bucket_assignment_boundaries():
    cfg = BucketConfig(batch_sizes=(1, 2, 4, 8), prompt_lens=(16, 64))
    assert cfg.batch_bucket(1) == 1
    assert cfg.batch_bucket(2) == 2
    assert cfg.batch_bucket(3) == 4          # rounds up, never down
    assert cfg.batch_bucket(8) == 8
    with pytest.raises(ValueError):
        cfg.batch_bucket(9)
    assert cfg.len_bucket(10) == 16
    assert cfg.len_bucket(16) == 16          # boundary is inclusive
    assert cfg.len_bucket(17) == 64
    assert cfg.len_bucket(100) == 100        # grid overflow -> exact fit
    # exact-fit default: every length is its own bucket
    assert BucketConfig().len_bucket(37) == 37
    with pytest.raises(ValueError):
        BucketConfig(batch_sizes=())
    with pytest.raises(ValueError):
        BucketConfig(batch_sizes=(0, 4))


def test_scheduler_assembles_and_flushes_greedily():
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
    for i in range(11):
        sched.submit(i, [5] * 10)
    ready = sched.ready()                    # one full 8-batch
    assert [mb.bucket for mb in ready] == [(8, 10)]
    assert ready[0].tags == list(range(8))
    rest = sched.flush()                     # 3 left -> greedy [2, 1]
    assert [mb.bucket for mb in rest] == [(2, 10), (1, 10)]
    assert len(sched) == 0
    st = sched.stats
    assert st.submitted == st.emitted == 11
    assert st.pad_rows == 0 and st.pad_fraction == 0.0
    assert st.occupancy == {(8, 10): 1, (2, 10): 1, (1, 10): 1}


def test_scheduler_pads_rows_and_lengths():
    from repro.data.tokenizer import PAD
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(4,),
                                             prompt_lens=(12,)))
    sched.submit("a", [7] * 9)
    sched.submit("b", [8] * 12)
    [mb] = sched.flush()
    assert mb.bucket == (4, 12) and mb.n_real == 2
    assert mb.tokens.shape == (4, 12)
    assert list(mb.tokens[0, :9]) == [7] * 9
    assert list(mb.tokens[0, 9:]) == [PAD] * 3       # length padding
    assert list(mb.tokens[2]) == [PAD] * 12          # row padding
    assert sched.stats.pad_rows == 2
    assert sched.stats.pad_tokens == 4 * 12 - 21
    with pytest.raises(ValueError):
        sched.submit("c", [])


def test_padded_rows_do_not_change_real_rows(tiny_trained):
    """Batch-axis padding parity: the decode scan is row-independent, so a
    bucket-padded batch reproduces the unpadded rows bit-for-bit."""
    from repro.data.tokenizer import PAD
    from repro.serving.sampler import generate
    cfg, params, _ = tiny_trained
    rng = np.random.default_rng(0)
    prompts = rng.integers(3, 100, size=(3, 20)).astype(np.int32)
    padded = np.full((8, 20), PAD, np.int32)
    padded[:3] = prompts
    g_ref, d_ref = generate(params, cfg, prompts, max_new_tokens=5)
    g_pad, d_pad = generate(params, cfg, padded, max_new_tokens=5)
    np.testing.assert_array_equal(g_pad[:3], g_ref)
    np.testing.assert_array_equal(d_pad[:3], d_ref)


def test_fixed_executable_reuse_across_batch_sizes(tiny_trained):
    """Within a bucket, varying per-step batch sizes must not compile new
    prefill/scan executables once the bucket shape is warm."""
    from repro.data.tokenizer import PAD
    from repro.serving.sampler import generate
    cfg, params, _ = tiny_trained
    rng = np.random.default_rng(1)
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(4,)))
    for step, n_real in enumerate((1, 3, 2, 4)):     # ragged steps, one bucket
        for r in range(n_real):
            sched.submit(f"{step}.{r}", rng.integers(3, 100, size=24).tolist())
        for mb in sched.flush():
            assert mb.tokens.shape == (4, 24)
            generate(params, cfg, mb.tokens, max_new_tokens=4)
        if step == 0:                        # first step compiled the bucket
            warm = decode_compile_counts()
    after = decode_compile_counts()
    assert after == warm, f"bucketed shapes recompiled: {warm} -> {after}"
    assert -1 not in warm.values()           # the counter API is available
    # a genuinely new shape DOES compile (sanity check of the counter)
    generate(params, cfg,
             np.full((3, 24), PAD, np.int32), max_new_tokens=4)
    assert decode_compile_counts() != after


# ---------------------------------------------------------------------------
# Stream vs batch predict through the engine
# ---------------------------------------------------------------------------
class CountingEstimator:
    """Deterministic stand-in: prediction is a pure function of the prompt."""

    def __init__(self):
        self.pairs = 0

    def predict(self, prompts, rng=None, **kw):
        self.pairs += len(prompts)
        out = []
        for p in prompts:
            h = sum(p) % 97
            out.append(Prediction(
                y_hat=h % 2, len_hat=64.0 + h, well_formed=True,
                p_conf=0.25 + 0.5 * (h / 97.0), pred_tokens=6,
                rationale_len=4))
        return out


@pytest.fixture()
def stream_setup(world, retriever, library):
    data = build_scope_data(world, n_queries=400, seed=5)

    def mk():
        return ScopeEngine.build(EngineConfig(
            estimator=CountingEstimator(), retriever=retriever,
            library=library,
            models_meta={m: world.models[m] for m in data.models}))
    return mk, data


def test_stream_matches_batch_predict_and_cache_stats(stream_setup):
    mk, data = stream_setup
    queries = [data.queries[int(q)] for q in data.test_qids[:17]]
    e_batch, e_stream = mk(), mk()
    pool = e_batch.predict(RouteRequest(queries))

    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
    ticks = [queries[0:4], queries[4:5], queries[5:12], queries[12:17]]
    pools = list(e_stream.predict_stream((RouteRequest(t) for t in ticks),
                                         scheduler=sched))
    assert len(pools) == len(ticks)
    # the ticks retrieve in other batch shapes than the one-shot predict:
    # the cross-shape contract (decisions exact, floats to ulp)
    for field in ("p_hat", "y_hat", "len_hat", "cost_hat", "well_formed",
                  "pred_overhead", "sims", "idx"):
        assert_cross_shape(
            np.concatenate([getattr(p, field) for p in pools]),
            getattr(pool, field), err_msg=field)
    M = len(data.models)
    assert [p.cache_misses for p in pools] == [4 * M, M, 7 * M, 5 * M]
    assert sum(p.cache_hits for p in pools) == 0
    assert sched.stats.emitted == 17 * M
    assert e_stream.config.estimator.pairs >= e_batch.config.estimator.pairs

    # warm re-stream: all hits, no estimator work, same values
    before = e_stream.config.estimator.pairs
    pools2 = list(e_stream.predict_stream(RouteRequest(t) for t in ticks))
    assert e_stream.config.estimator.pairs == before
    assert [p.cache_hits for p in pools2] == [4 * M, M, 7 * M, 5 * M]
    np.testing.assert_array_equal(
        np.concatenate([p.p_hat for p in pools2]), pool.p_hat)


def test_stream_small_ticks_ride_along_and_empty_ticks(stream_setup):
    mk, data = stream_setup
    queries = [data.queries[int(q)] for q in data.test_qids[:6]]
    engine = mk()
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(8,)))
    ticks = [queries[:1], [], queries[1:6]]
    pools = list(engine.predict_stream((RouteRequest(t) for t in ticks),
                                       scheduler=sched))
    assert [p.p_hat.shape[0] for p in pools] == [1, 0, 5]
    # the 1-query tick couldn't fill a bucket alone: it was held and shipped
    # together with the later traffic (cross-request microbatching)
    assert sched.stats.microbatches > 0
    ref = mk().predict(RouteRequest(queries))
    np.testing.assert_array_equal(
        np.concatenate([p.p_hat for p in pools]), ref.p_hat)


def test_stream_dedupes_inflight_duplicate_queries(stream_setup):
    """A hot query repeated across ticks while still in flight shares the
    first tick's generation instead of scheduling a duplicate prompt."""
    mk, data = stream_setup
    q = data.queries[int(data.test_qids[0])]
    engine = mk()
    # bucket larger than one tick's prompts: tick 1 is still queued when
    # tick 2 repeats the same query
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(8,)))
    pools = list(engine.predict_stream(
        (RouteRequest(t) for t in ([q], [q])), scheduler=sched))
    M = len(data.models)
    assert sched.stats.submitted == M            # duplicates not scheduled
    assert engine.config.estimator.pairs == 8    # one padded microbatch
    np.testing.assert_array_equal(pools[0].p_hat, pools[1].p_hat)
    assert pools[0].pred_overhead.sum() > 0
    assert pools[1].pred_overhead.sum() == 0     # shared: no new tokens
    ref = mk().predict(RouteRequest([q]))
    np.testing.assert_array_equal(pools[1].p_hat, ref.p_hat)
    # the cache keeps the primary's true token spend, not the rider's 0
    from repro.api.cache import query_key
    cached = engine.cache.get(query_key(q), data.models[0],
                              engine.config.estimator_version)
    assert cached is not None and cached.pred_tokens > 0
    # uncached streams never share work
    e2 = mk()
    sched2 = MicrobatchScheduler(BucketConfig(batch_sizes=(8,)))
    list(e2.predict_stream((RouteRequest(t) for t in ([q], [q])),
                           scheduler=sched2, use_cache=False))
    assert sched2.stats.submitted == 2 * M


def test_predict_empty_request_skips_model_validation(stream_setup):
    """Zero-query predict returns an empty pool even for a model that is
    not onboarded yet (validation applies to non-empty requests only)."""
    mk, data = stream_setup
    engine = mk()
    pool = engine.predict(RouteRequest([], models=["not-onboarded"]))
    assert pool.p_hat.shape == (0, 1)
    q = data.queries[int(data.test_qids[0])]
    with pytest.raises(KeyError):
        engine.predict(RouteRequest([q], models=["not-onboarded"]))


def test_serve_stream_matches_serve(stream_setup):
    mk, data = stream_setup
    qids = [int(q) for q in data.test_qids[:12]]
    policy = FixedAlphaPolicy(0.6)
    rep = mk().serve(data, qids, policy)
    reports = list(mk().serve_stream(data, [qids[:7], qids[7:]], policy))
    assert len(reports) == 2
    assert all(r.executed for r in reports)
    assert sum(r.n_queries for r in reports) == len(qids)
    got = [d.model for r in reports for d in r.decisions]
    want = [d.model for d in rep.decisions]
    assert got == want
    total = sum(r.total_cost for r in reports)
    assert total == pytest.approx(rep.total_cost)


# ---------------------------------------------------------------------------
# Sharded multi-device serving (subprocess: isolated device-count flag)
# ---------------------------------------------------------------------------
SUBPROC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, json
import numpy as np
from repro.api import EngineConfig, RouteRequest, ScopeEngine
from repro.configs.scope_estimator import TINY
from repro.core.estimator import ReasoningEstimator
from repro.core.fingerprint import FingerprintLibrary, build_anchor_set
from repro.core.retrieval import AnchorRetriever
from repro.data.datasets import build_scope_data, stratified_anchors
from repro.data.worldsim import World
from repro.launch.mesh import make_serve_mesh
from repro.models import model as M
from repro.serving.scheduler import BucketConfig, MicrobatchScheduler

world = World(seed=0)
data = build_scope_data(world, n_queries=120, seed=0)
aset = build_anchor_set(world, stratified_anchors(world, n=40, seed=7))
lib = FingerprintLibrary(aset)
for m in data.models:
    lib.onboard(world, m, seed=3)
params = M.init_params(jax.random.PRNGKey(0), TINY)

def mk():
    return ScopeEngine.build(EngineConfig(
        estimator=ReasoningEstimator(TINY, params),
        retriever=AnchorRetriever(aset), library=lib,
        models_meta={m: world.models[m] for m in data.models}))

queries = [data.queries[int(q)] for q in data.test_qids[:4]]
ref = mk().predict(RouteRequest(queries))

mesh = make_serve_mesh()
engine = mk()
engine.estimator.shard(mesh)
sched = MicrobatchScheduler(BucketConfig(batch_sizes=(4, 8)))
ticks = [queries[:1], queries[1:4]]
pools = list(engine.predict_stream((RouteRequest(t) for t in ticks),
                                   scheduler=sched))
cat = lambda f: np.concatenate([getattr(p, f) for p in pools]).tolist()
embed = engine.estimator.params["embed"]
print(json.dumps({
    "devices": jax.local_device_count(),
    "mesh_data": int(mesh.devices.shape[0]),
    "embed_devices": len(embed.sharding.device_set),
    "got": {f: cat(f) for f in ("p_hat", "y_hat", "cost_hat", "well_formed")},
    "want": {f: getattr(ref, f).tolist()
             for f in ("p_hat", "y_hat", "cost_hat", "well_formed")},
    "hits_misses": [[p.cache_hits, p.cache_misses] for p in pools],
    "n_models": len(data.models),
    "microbatches": sched.stats.microbatches,
    "faults": sched.stats.as_dict()["faults"],
}))
"""


def test_stream_predict_sharded_multi_device_matches_single():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SUBPROC_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4 and res["mesh_data"] == 4
    assert res["embed_devices"] == 4, "params not spread over the mesh"
    # every pair decoded for real: a device failure that degraded the
    # whole stream to retrieval priors must not pass as a match
    faults = res["faults"]
    assert faults["degraded_fraction"] == 0.0, faults
    assert faults["retries"] == 0 and faults["unexpected"] == 0, faults
    # 4 devices vs 1: another shape, so the cross-shape contract
    for f, want in res["want"].items():
        assert_cross_shape(np.asarray(res["got"][f]), np.asarray(want),
                           err_msg=f"sharded stream vs 1-device: {f}")
    M_ = res["n_models"]
    assert res["hits_misses"] == [[0, 1 * M_], [0, 3 * M_]]
    assert res["microbatches"] > 0


# ---------------------------------------------------------------------------
# _pad_caches: explicit seq-axis contract (regression for axis sniffing)
# ---------------------------------------------------------------------------
def test_pad_caches_adversarial_shapes():
    """Shapes engineered so prompt_len coincides with head count, conv
    width, SSM state dim, and the encoder cross-cache seq — the old
    axis-sniffing implementation pads the wrong axis on every one."""
    lp, new, L, b = 4, 6, 2, 3                  # prompt_len == 4 everywhere
    caches = ({
        "0": {
            # kv_heads == prompt_len: seq is axis 3, NOT the head axis
            "k": jnp.zeros((L, b, lp, lp, 8)),
            "v": jnp.zeros((L, b, lp, lp, 8)),
            # conv width-1 == prompt_len: mamba state, never grown
            "conv": jnp.zeros((L, b, lp, 16)),
            # ssm state dim == prompt_len: never grown
            "ssm": jnp.zeros((L, b, 2, 8, lp)),
            # encoder cross cache with enc_seq == prompt_len: never grown
            "ck": jnp.zeros((L, b, 2, lp, 8)),
            "cv": jnp.zeros((L, b, 2, lp, 8)),
        },
        "1": {
            # MLA latent caches: seq is axis 2
            "c_kv": jnp.zeros((L, b, lp, 16)),
            "k_rope": jnp.zeros((L, b, lp, lp)),
        },
    },)
    out = _pad_caches(caches, lp + new, lp)
    leaf = out[0]["0"]
    assert leaf["k"].shape == (L, b, lp, lp + new, 8)
    assert leaf["v"].shape == (L, b, lp, lp + new, 8)
    assert leaf["conv"].shape == (L, b, lp, 16)
    assert leaf["ssm"].shape == (L, b, 2, 8, lp)
    assert leaf["ck"].shape == (L, b, 2, lp, 8)
    assert leaf["cv"].shape == (L, b, 2, lp, 8)
    mla = out[0]["1"]
    assert mla["c_kv"].shape == (L, b, lp + new, 16)
    assert mla["k_rope"].shape == (L, b, lp + new, lp)


def test_pad_caches_rejects_seq_mismatch():
    caches = ({"0": {"k": jnp.zeros((1, 1, 2, 9, 4))}},)
    with pytest.raises(ValueError, match="seq axis"):
        _pad_caches(caches, 16, prompt_len=8)


def test_generate_with_prompt_len_equal_to_head_count(tiny_trained):
    """End-to-end: a prompt whose length equals the KV head count decodes
    correctly (the sniffing version grew the head axis instead)."""
    from repro.serving.sampler import generate
    cfg, params, _ = tiny_trained
    lp = cfg.num_kv_heads
    rng = np.random.default_rng(2)
    prompts = rng.integers(3, 100, size=(2, lp)).astype(np.int32)
    gen, dec = generate(params, cfg, prompts, max_new_tokens=4)
    assert gen.shape == (2, 4) and dec.shape == (2, 4, 2)
