"""DeepSeek-V2's blocks on the paged serve path, against the plain
reference: YaRN rope, MLA latent pages, and the drop-free expert layer
that holds a share of the routed experts.

The config is DeepSeek-V2-Lite's family at a tiny width: YaRN, a first
dense layer, 8 routed experts, top-2, one shared expert, and 4 of the 8
experts held here.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EngineConfig, RouteRequest, ScopeEngine
from repro.configs import get_config
from repro.configs.scope_estimator import CONFIG as QWEN3_4B
from repro.core.estimator import ReasoningEstimator
from repro.data.datasets import build_scope_data
from repro.models import attention as attn_mod
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.models import reference as R
from repro.models import rope as rope_mod
from repro.serving import sampler
from repro.serving.kv_pool import (KVPool, check_paged_support,
                                   kv_bytes_per_token)
from repro.serving.scheduler import BucketConfig, MicrobatchScheduler

DEEPSEEK = get_config("deepseek-v2-lite-16b")
TINY = dataclasses.replace(
    DEEPSEEK, num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
    d_ff=128, vocab_size=512, num_experts=8, num_experts_per_tok=2,
    num_shared_experts=1, moe_d_ff=32, experts_held=4, expert_offset=0,
    kv_lora_rank=32, qk_rope_head_dim=16, qk_nope_head_dim=16,
    v_head_dim=16, dtype="float32", yarn_original_max_position=64)
B, L, T, PAGE = 4, 12, 8, 4


def _params(cfg, seed=0):
    return M.init_params(jax.random.PRNGKey(seed), cfg)


def _prompts(cfg, seed=0):
    rng = np.random.default_rng(seed)
    prompts = rng.integers(3, cfg.vocab_size, size=(B, L)).astype(np.int32)
    return prompts, np.array([L, L - 3, L - 7, 5])


def _pool():
    return KVPool(n_pages=B * -(-(L + T) // PAGE), page_size=PAGE)


def _reference_decisions(params, cfg, prompts, lens, gen):
    """The reference's (YES, NO) logits before each served token."""
    seq = np.zeros((B, L + T), np.int32)
    for i in range(B):
        seq[i, : lens[i]] = prompts[i, : lens[i]]
        seq[i, lens[i]: lens[i] + T] = gen[i]
    h = R.hidden(params, cfg, jnp.asarray(seq))
    cols = (lens - 1)[:, None] + np.arange(T)[None]
    return np.asarray(R.head(params, cfg, h[np.arange(B)[:, None], cols],
                             sampler.DECISION_TOKENS))


# ---------------------------------------------------------------------------
# the serve path against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("offset", [0, 4])
def test_prefill_then_paged_decode_match_the_reference(dtype, offset):
    """The paged prefill, then paged decode segments, against one
    reference forward over prompt and served tokens."""
    cfg = dataclasses.replace(TINY, dtype=dtype, expert_offset=offset)
    params = _params(cfg)
    prompts, lens = _prompts(cfg)
    st = sampler.prefill_state(params, cfg, prompts, max_new_tokens=T,
                               prompt_lens=lens, kv_pool=_pool())
    last = np.asarray(st.last_logits)
    _, gen, dec = sampler.decode_segment(params, cfg, st, T)
    res = R.serve_parity(params, cfg, prompts, lens, last, gen, dec,
                         sampler.DECISION_TOKENS)
    assert R.parity_failures(res, dtype) == [], res
    if dtype == "bfloat16":
        # rounding to bf16 is visible: the f32 bound would not hold
        assert R.parity_failures(res, "float32")


def test_fused_refill_executables_match_the_reference():
    """The serve runtime's own executables: a state opened with nothing
    prefilled, its rows admitted by the fused refill launch, then a plain
    segment, decoding from latent pages."""
    params = _params(TINY)
    prompts, lens = _prompts(TINY)
    st = sampler.open_state(params, TINY, prompts, max_new_tokens=T,
                            kv_pool=_pool())
    st, g1, d1 = sampler.decode_segment(
        params, TINY, st, T // 2, refill=(np.ones(B, bool), prompts, lens))
    st, g2, d2 = sampler.decode_segment(params, TINY, st, T // 2)
    gen = np.concatenate([np.asarray(g1), np.asarray(g2)], 1)
    dec = np.concatenate([np.asarray(d1), np.asarray(d2)], 1)
    want = _reference_decisions(params, TINY, prompts, lens, gen)
    errs = R.rel_errors(dec, want)
    assert errs["rel_max"] < R.TOLERANCE["float32"]["rel_max"], errs
    # the launch's counters: every token routes num_experts_per_tok times
    # per expert layer, min(k, held) of them at most to held experts
    routed = np.asarray(st.stats["expert_tokens_decode"])
    assert routed.shape == (M.expert_layers(TINY), TINY.experts_held)
    assert 0 < routed.sum(1).max() <= B * (T // 2) * 2


def test_paged_decode_matches_dense_mla_decode():
    """Latent pages hold what the dense latent cache holds: the paged and
    dense absorbed decodes give the same logits."""
    params = _params(TINY)
    prompts, lens = _prompts(TINY)
    outs = []
    for kw in ({}, {"kv_pool": _pool()}):
        st = sampler.prefill_state(params, TINY, prompts, max_new_tokens=T,
                                   prompt_lens=lens, **kw)
        _, gen, dec = sampler.decode_segment(params, TINY, st, T)
        outs.append((np.asarray(gen), np.asarray(dec)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-6, atol=1e-6)


def test_reference_matches_the_full_forward():
    """The reference and the program's own full forward (capacity wide
    enough that nothing drops) agree on the uncut and the cut model."""
    for cfg in (dataclasses.replace(TINY, experts_held=0), TINY):
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
        params = _params(cfg, seed=1)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                  cfg.vocab_size)
        want, _ = M.forward_train(params, cfg, {"tokens": toks})
        got = R.head(params, cfg, R.hidden(params, cfg, toks))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
def _layer_inputs(cfg, seed=3, rows=6, seq=5):
    p = moe_mod.init_moe(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (rows, seq, cfg.d_model), jnp.float32)
    return p, x


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_expert_shares_sum_to_the_uncut_layer(shares):
    """Each chip of an expert-parallel deployment computes its own
    experts' part plus the shared expert: over all chips, with the shared
    expert counted once, the parts give the uncut reference layer."""
    full = dataclasses.replace(TINY, experts_held=0)
    p, x = _layer_inputs(full)
    want = R._moe(p, full, x)
    got, _ = moe_mod.moe_dropless(p, full, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    held = full.num_experts // shares
    shared = R._swiglu(p["shared"], x)
    total = -(shares - 1) * shared
    for j in range(shares):
        cfg = dataclasses.replace(full, experts_held=held,
                                  expert_offset=j * held)
        pj = dict(p, **{k: p[k][j * held:(j + 1) * held]
                        for k in ("wi_gate", "wi_up", "wo")})
        part, routed = moe_mod.moe_dropless(pj, cfg, x)
        np.testing.assert_allclose(np.asarray(part),
                                   np.asarray(R._moe(pj, cfg, x)),
                                   rtol=1e-5, atol=1e-5)
        assert routed.shape == (held,)
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seq", [1, 5])
def test_a_rows_output_does_not_depend_on_the_other_rows(seq):
    """Drop-free: row 0 gives the same output whatever shares its batch,
    where the capacity dispatch drops tokens and moves it."""
    p, x = _layer_inputs(TINY, rows=16, seq=seq)
    # every other row a copy of row 1: all pile onto the same experts
    crowd = x.at[2:].set(jnp.broadcast_to(x[1], x[2:].shape))
    alone, _ = moe_mod.moe_dropless(p, TINY, x[:1])
    for batch in (x, crowd):
        y, _ = moe_mod.moe_dropless(p, TINY, batch)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(alone[0]),
                                   rtol=1e-6, atol=1e-6)
    # capacity dispatch: copies of row 0 ahead of it fill its experts
    copies = jnp.broadcast_to(x[:1], x.shape)
    y_copies, _ = moe_mod.moe_dropless(p, TINY, copies)
    np.testing.assert_allclose(np.asarray(y_copies[-1]),
                               np.asarray(alone[0]), rtol=1e-6, atol=1e-6)
    tight = dataclasses.replace(TINY, capacity_factor=1.0)
    y_alone, _ = moe_mod.moe_forward(p, tight, x[:1])
    y_crowd, _ = moe_mod.moe_forward(p, tight, copies)
    assert not np.allclose(np.asarray(y_crowd[-1]), np.asarray(y_alone[0]),
                           atol=1e-4)


def test_unrouted_tokens_get_the_shared_experts_only():
    """A token the mask leaves out (padding, a filler row) adds no expert
    block and no count; the routed tokens' outputs do not change."""
    p, x = _layer_inputs(TINY, rows=4, seq=6)
    routed = jnp.asarray(np.arange(6)[None] < np.array([6, 3, 1, 0])[:, None])
    whole, counts = moe_mod.moe_dropless(p, TINY, x)
    y, got = moe_mod.moe_dropless(p, TINY, x, routed)
    keep = np.asarray(routed)
    np.testing.assert_allclose(np.asarray(y)[keep], np.asarray(whole)[keep],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[~keep],
                               np.asarray(R._swiglu(p["shared"], x))[~keep],
                               rtol=1e-6, atol=1e-6)
    _, _, top_i = moe_mod.route(p, TINY, x)
    mine = (np.asarray(top_i) < TINY.experts_held) & keep[..., None]
    want = np.bincount(np.asarray(top_i)[mine],
                       minlength=TINY.experts_held)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert np.asarray(got).sum() < np.asarray(counts).sum()


def test_a_refill_routes_its_prompts_tokens_only():
    """The fused refill of 3 prompts in a 4-row bucket: its prefill count
    is that of the 3 prompts prefilled alone at their lengths — the filler
    row and the padding route nothing."""
    params = _params(TINY)
    prompts, lens = _prompts(TINY)
    st = sampler.open_state(params, TINY, prompts, max_new_tokens=T,
                            kv_pool=_pool())
    admit = np.array([True, True, True, False])
    st, _, _ = sampler.decode_segment(params, TINY, st, T // 2,
                                      refill=(admit, prompts, lens))
    got = np.asarray(st.stats["expert_tokens_prefill"])
    want = sum(np.asarray(M.prefill(params, TINY,
                                    {"tokens": prompts[i: i + 1, : lens[i]]},
                                    with_stats=True)[2]["expert_tokens"])
               for i in np.flatnonzero(admit))
    np.testing.assert_array_equal(got, want)


def test_the_gate_follows_norm_topk_prob():
    """DeepSeek-V2 weights its experts by the softmax at the chosen ones;
    Qwen3-MoE renormalises them over the top-k."""
    p, x = _layer_inputs(TINY)
    _, raw, _ = moe_mod.route(p, TINY, x)
    _, norm, _ = moe_mod.route(
        p, dataclasses.replace(TINY, norm_topk_prob=True), x)
    assert DEEPSEEK.norm_topk_prob is False
    assert float(jnp.max(jnp.sum(raw, -1))) < 1.0
    np.testing.assert_allclose(np.asarray(jnp.sum(norm, -1)), 1.0,
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------
def test_yarn_matches_the_closed_form():
    """DeepSeek-V2-Lite's published YaRN: pairs up to the fast correction
    index keep their frequency, those past the slow one are divided by 40,
    a linear ramp between; the softmax scale is 192^-0.5 mscale^2 with
    mscale = 0.1 x 0.707 x ln 40 + 1, and cos/sin are not scaled."""
    d, theta = 64, 10000.0
    base = 1.0 / theta ** (np.arange(0, d, 2) / d)

    def index(r):
        return d * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(theta))

    lo, hi = math.floor(index(32)), math.ceil(index(1))
    assert (lo, hi) == (10, 23)
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0, 1)
    want = base * (1 - ramp) + base / 40 * ramp
    got = rope_mod.yarn_frequencies(d, theta, 40.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-12)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-12)
    np.testing.assert_allclose(R._yarn_inv_freq(DEEPSEEK, d), got,
                               rtol=1e-12)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert attn_mod.mla_softmax_scale(DEEPSEEK) == pytest.approx(
        192 ** -0.5 * m * m, rel=1e-12)
    assert attn_mod.mla_softmax_scale(DEEPSEEK) == pytest.approx(0.11472,
                                                                 abs=1e-5)
    cos, sin = M._rope(DEEPSEEK, jnp.arange(5)[None], d)
    ang = np.arange(5)[:, None] * got[None]
    np.testing.assert_allclose(np.asarray(cos[0]),
                               np.cos(np.concatenate([ang, ang], -1)),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# latent pages in the pool
# ---------------------------------------------------------------------------
def test_latent_page_accounting():
    """An MLA layer caches its latent: (kv_lora_rank + qk_rope_head_dim)
    x 2 B a token at bf16; one page id spans every layer's latent pages."""
    assert kv_bytes_per_token(DEEPSEEK) == 27 * (512 + 64) * 2 == 31104
    assert kv_bytes_per_token(QWEN3_4B) == 36 * 2 * 8 * 128 * 2 == 147456
    assert kv_bytes_per_token(TINY) == 3 * (32 + 16) * 4
    params = _params(TINY)
    prompts, lens = _prompts(TINY)
    pool = _pool()
    st = sampler.prefill_state(params, TINY, prompts, max_new_tokens=T,
                               prompt_lens=lens, kv_pool=pool)
    pages = -(-lens // PAGE)
    assert pool.pages_in_use == pages.sum()
    assert pool.live_tokens == lens.sum()
    for seg, (unit, count) in zip(st.caches, [(("mla",), 1),
                                              (("mla_moe",), 2)]):
        assert seg["0"]["c_kv"].shape == (count, pool.n_pages + 1, PAGE,
                                          TINY.kv_lora_rank)
        assert seg["0"]["k_rope"].shape == (count, pool.n_pages + 1, PAGE,
                                            TINY.qk_rope_head_dim)
    st, _, _ = sampler.decode_segment(params, TINY, st, T)
    assert pool.pages_in_use == (-(-(lens + T) // PAGE)).sum()
    for row in range(B):
        st.paged.retire_row(row)
    assert pool.pages_in_use == 0 and pool.reserved == 0


@pytest.mark.parametrize("arch,ok", [
    ("deepseek-v2-lite-16b", True), ("internlm2-1.8b", True),
    ("mamba2-1.3b", False), ("zamba2-7b", False), ("gemma2-2b", False)])
def test_paged_support_takes_latents_and_rejects_ssm_and_windows(arch, ok):
    cfg = get_config(arch).reduced()
    if ok:
        check_paged_support(cfg)
    else:
        with pytest.raises(ValueError, match="paged"):
            check_paged_support(cfg)


# ---------------------------------------------------------------------------
# named scopes and the routed-token counter on the serve path
# ---------------------------------------------------------------------------
def test_decode_executable_names_the_expert_and_latent_scopes():
    params = _params(TINY)
    prompts, _ = _prompts(TINY)
    pool = _pool()
    st = sampler.open_state(params, TINY, prompts, max_new_tokens=T,
                            kv_pool=pool)
    pg = st.paged
    text = sampler._paged_scan_decode.lower(
        params, TINY, st.last_logits, st.caches, jax.random.PRNGKey(0), 4,
        0.0, True, pg.spec, pg.device_table(), st.positions,
        st.done).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    # the layer scan puts its own loop between ``decode`` and the layer
    for scope in ("/attn/latent/", "/moe/route/", "/moe/experts/",
                  "/moe/shared/", "/mlp/"):
        assert any("/decode/" in n and scope in n for n in names), scope


def test_stream_serves_and_counts_routed_tokens(world, retriever, library):
    """``ScopeEngine.predict_stream`` on the paged refill path with the
    cut DeepSeek backbone: every pair answered, and the tokens routed to
    each held expert folded into ``SchedulerStats`` per phase."""
    data = build_scope_data(world, n_queries=40, seed=9)
    queries = [data.queries[int(q)] for q in data.test_qids[:4]]
    est = ReasoningEstimator(TINY, _params(TINY), max_new_tokens=6)
    engine = ScopeEngine.build(EngineConfig(
        estimator=est, retriever=retriever, library=library,
        models_meta={m: world.models[m] for m in data.models},
        refill=True, segment_len=3, kv_page_size=8))
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(8,)))
    ticks = [queries[:1], queries[1:3], [], queries[3:], []]
    pools = list(engine.predict_stream(iter([RouteRequest(t) for t in ticks]),
                                       scheduler=sched))
    answered = sum(p.p_hat.size for p in pools)
    assert answered == len(queries) * len(data.models)
    st = sched.stats
    layers, held = M.expert_layers(TINY), TINY.experts_held
    assert st.expert_tokens_prefill.shape == (layers, held)
    assert st.expert_tokens_decode.shape == (layers, held)
    # each decode slot-step routes k choices per layer, some held here
    k = TINY.num_experts_per_tok
    assert 0 < st.expert_tokens_decode.sum() <= st.slot_steps_total * k * \
        layers
    summary = st.as_dict()["expert_tokens"]
    assert set(summary) == {"prefill", "decode"}
    assert summary["decode"]["max_over_mean"] >= 1.0
