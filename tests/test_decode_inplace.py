"""The paged decode executables update the page pool in place, and a
refill's prefill lands in the token-major pages where decode reads it.

The layer scan carries the stacked pool and each layer writes its new
token into its own index of it, and each launch donates the pool it takes:
the compiled executable hands the pool's input buffers to its outputs and
holds no pool-sized copy, broadcast, slice or transpose.  A donated pool is
dead, so every caller decodes from the pool a launch returned.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EngineConfig, RouteRequest, ScopeEngine
from repro.configs import get_config
from repro.configs.scope_estimator import TINY as GQA
from repro.core.estimator import ReasoningEstimator
from repro.data.datasets import build_scope_data
from repro.launch import hlo_analysis as H
from repro.models import model as M
from repro.serving import sampler
from repro.serving.kv_pool import KVPool
from repro.serving.scheduler import BucketConfig, MicrobatchScheduler

MLA = dataclasses.replace(
    get_config("deepseek-v2-lite-16b"), num_layers=3, d_model=64,
    num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=512, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, moe_d_ff=32,
    experts_held=4, expert_offset=0, kv_lora_rank=32, qk_rope_head_dim=16,
    qk_nope_head_dim=16, v_head_dim=16, dtype="float32",
    yarn_original_max_position=64)
CONFIGS = {"gqa": GQA, "mla": MLA}
B, WIDTH, BUDGET, PAGE, STEPS = 4, 12, 8, 4, 4
# what a launch may not do with the pool, or with one layer of it
POOL_OPS = {"copy", "copy-start", "broadcast", "dynamic-slice", "transpose"}


@pytest.fixture(scope="module")
def params():
    return {name: M.init_params(jax.random.PRNGKey(0), cfg)
            for name, cfg in CONFIGS.items()}


def _pool():
    # 21 pages with the trash page: a size no weight or activation has
    return KVPool(n_pages=B * -(-(WIDTH + BUDGET) // PAGE), page_size=PAGE)


def _prompts(cfg, seed):
    rng = np.random.default_rng(seed)
    prompts = rng.integers(3, cfg.vocab_size, size=(B, WIDTH))
    return prompts.astype(np.int32), np.array([WIDTH, WIDTH - 3, 5, WIDTH])


def _compiled(params, cfg, name):
    """The compiled executable ``name`` of a freshly opened state, and the
    shapes of its pool's leaves."""
    st = sampler.open_state(params, cfg, np.zeros((B, WIDTH), np.int32),
                            max_new_tokens=BUDGET, kv_pool=_pool())
    pg = st.paged
    args = (params, cfg, st.last_logits, st.caches, jax.random.PRNGKey(0),
            STEPS, 0.0, True, pg.spec, pg.device_table(), st.positions,
            st.done)
    if name == "_paged_refill_scan_decode":
        rows = np.full((B // 4,), B, np.int32)
        args += sampler._refill_operands(
            rows, np.zeros((B, WIDTH), np.int32), np.ones((B,), np.int64))
        args += (jnp.asarray(pg.prompt_page_ids(
            rows, -(-WIDTH // PAGE)).reshape(-1)),)
    text = getattr(sampler, name).lower(*args).compile().as_text()
    return text, [tuple(x.shape) for x in jax.tree.leaves(st.caches)]


@pytest.mark.parametrize("name", ["_paged_scan_decode",
                                  "_paged_refill_scan_decode"])
@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_paged_executables_update_the_pool_in_place(params, arch, name):
    text, leaves = _compiled(params[arch], CONFIGS[arch], name)
    pool_params = [i for i, shape in H.entry_parameters(text).items()
                   if shape in set(leaves)]
    assert len(pool_params) == len(leaves)
    # every pool leaf's buffer is handed to the output it becomes
    assert set(pool_params) <= set(H.aliased_parameters(text))
    # no step or layer rebuilds, slices or relays the pool (or one layer)
    shapes = set(leaves) | {shape[1:] for shape in leaves}
    assert H.ops_shaped_like(text, POOL_OPS, shapes) == []


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_a_warmed_state_decodes_as_one_never_warmed(params, arch,
                                                    monkeypatch):
    """``warm_row_buckets`` keeps the pool its launches return (they donate
    the state's): the state then admits and decodes bit-identically to a
    state opened the same way and never warmed."""
    monkeypatch.setattr(sampler, "_WARMED", set())
    cfg, p = CONFIGS[arch], params[arch]
    prompts, lens = _prompts(cfg, 1)
    out = []
    for warm in (False, True):
        st = sampler.open_state(p, cfg, prompts, max_new_tokens=BUDGET,
                                kv_pool=_pool())
        if warm:
            opened = st.caches
            sampler.warm_row_buckets(p, cfg, st, WIDTH, STEPS)
            # the warm-up launched, and its launches took the opened pool
            assert len(sampler._WARMED) == 1
            assert all(x.is_deleted() for x in jax.tree.leaves(opened))
        st, g1, d1 = sampler.decode_segment(
            p, cfg, st, STEPS, refill=(np.array([1, 1, 0, 1], bool),
                                       prompts, lens))
        st, g2, d2 = sampler.decode_segment(p, cfg, st, STEPS)
        out.append([np.asarray(x) for x in
                    (g1, d1, g2, d2, st.positions, st.done)])
    for cold, warm in zip(*out):
        np.testing.assert_array_equal(cold, warm)


def _recording(monkeypatch):
    """Patch ``sampler.decode_segment`` to keep every pool it is given."""
    given = []
    segment = sampler.decode_segment

    def recorded(params, cfg, state, steps, **kw):
        given.append(state.caches)
        return segment(params, cfg, state, steps, **kw)

    monkeypatch.setattr(sampler, "decode_segment", recorded)
    return given


def test_a_slot_run_never_reads_a_donated_pool(params, monkeypatch):
    """A paged ``SlotRun`` admits and decodes through several launches:
    each launch's pool is dead after it (donated), and the run still
    serves every request, reading only the pools launches returned."""
    given = _recording(monkeypatch)
    est = ReasoningEstimator(GQA, params["gqa"], max_new_tokens=BUDGET)
    prompts, _ = _prompts(GQA, 2)
    rng = np.random.default_rng(3)
    queue = [(f"q{i}", list(rng.integers(3, 100, size=WIDTH - i)))
             for i in range(5)]
    pool = KVPool(n_pages=32, page_size=PAGE)
    run = est.open_slots(prompts, tags=["a", "b"], kv_pool=pool,
                         segment_len=STEPS)
    done = []
    while not run.finished or queue:
        if queue and run.free_rows() and run.can_admit():
            n = min(len(queue), len(run.free_rows()))
            run.admit([(t, q, len(q)) for t, q in queue[:n]])
            del queue[:n]
        done += run.step()[0]
    assert sorted(done) == ["a", "b", "q0", "q1", "q2", "q3", "q4"]
    assert len(given) > 3
    assert all(x.is_deleted() for c in given for x in jax.tree.leaves(c))
    assert not any(x.is_deleted() for x in jax.tree.leaves(run.state.caches))
    assert pool.pages_in_use == 0


def test_the_stream_never_reads_a_donated_pool(params, world, retriever,
                                               library, monkeypatch):
    """``predict_stream`` on the paged refill path (latent pages): every
    pair answered, and every pool a launch took is dead after it."""
    given = _recording(monkeypatch)
    data = build_scope_data(world, n_queries=40, seed=9)
    queries = [data.queries[int(q)] for q in data.test_qids[:4]]
    est = ReasoningEstimator(MLA, params["mla"], max_new_tokens=6)
    engine = ScopeEngine.build(EngineConfig(
        estimator=est, retriever=retriever, library=library,
        models_meta={m: world.models[m] for m in data.models},
        refill=True, segment_len=3, kv_page_size=8))
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(8,)))
    ticks = [queries[:1], queries[1:3], [], queries[3:], []]
    pools = list(engine.predict_stream(iter([RouteRequest(t) for t in ticks]),
                                       scheduler=sched))
    assert sum(p.p_hat.size for p in pools) == \
        len(queries) * len(data.models)
    assert len(given) > 2
    assert all(x.is_deleted() for c in given for x in jax.tree.leaves(c))


def test_prefill_pages_hold_the_dense_prefill(params):
    """A refill's page scatter (``_scatter_prefill_caches``, the fused
    refill's own) puts each admitted row's dense prefill K and V at token
    slot ``t % PAGE`` of its page ``table[row, t // PAGE]``, the whole
    (hkv, hd) slab; the bucket's filler row writes only the trash page and
    no other page is touched."""
    cfg, p = GQA, params["gqa"]
    prompts, lens = _prompts(cfg, 4)
    st = sampler.open_state(p, cfg, np.zeros((B, WIDTH), np.int32),
                            max_new_tokens=BUDGET, kv_pool=_pool())
    pg = st.paged
    admitted = [0, 2, 3]
    for row in admitted:
        pg.admit_row(row, int(lens[row]))
    rows = sampler.bucket_rows(admitted, B)
    assert list(rows) == admitted + [B]             # one filler entry
    npg = -(-WIDTH // PAGE)
    ids = pg.prompt_page_ids(rows, npg).reshape(-1)
    take = np.minimum(rows, B - 1)
    _, dense = M.prefill(p, cfg, {"tokens": jnp.asarray(prompts[take])},
                         jnp.asarray(lens[take]))
    n_total = pg.pool.n_pages + 1
    pages = sampler._scatter_prefill_caches(
        dense, lambda path, leaf: sampler._paged_storage(leaf, n_total, PAGE),
        jnp.asarray(ids), PAGE)
    owned = {int(pg.table[r, w]) for r in admitted
             for w in range(-(-int(lens[r]) // PAGE))}
    untouched = sorted(set(range(pg.pool.n_pages)) - owned)
    pairs = list(zip(jax.tree.leaves(dense), jax.tree.leaves(pages)))
    assert len(pairs) == 2                      # K and V of the one stack
    for leaf, paged in pairs:
        leaf, paged = np.asarray(leaf), np.asarray(paged)
        assert paged.shape == (cfg.num_layers, n_total, PAGE,
                               cfg.num_kv_heads, cfg.resolved_head_dim)
        for i, row in enumerate(admitted):
            for t in range(int(lens[row])):
                np.testing.assert_array_equal(
                    paged[:, pg.table[row, t // PAGE], t % PAGE],
                    leaf[:, i, :, t])
        assert not paged[:, untouched].any()
