"""Continuous-batching serve runtime: per-row ragged decode, DecodeState
segments + slot refill, the double-buffered ServeRuntime, and the engine's
overlapped predict_stream (incl. ragged-length grid parity)."""
import functools

import numpy as np
import pytest

import jax

from repro.api import EngineConfig, RouteRequest, ScopeEngine
from repro.core.estimator import (
    DecodeHandle, ReasoningEstimator, parse_generations)
from repro.data import tokenizer as tok
from repro.data.datasets import build_scope_data
from repro.serving import sampler
from repro.serving.runtime import ServeRuntime
from repro.serving.scheduler import BucketConfig, Microbatch, MicrobatchScheduler


# ---------------------------------------------------------------------------
# Per-row positions / ragged prompt lengths in the sampler
# ---------------------------------------------------------------------------
def test_generate_ragged_lengths_match_unpadded(tiny_trained):
    """Sub-bucket rows reproduce the unpadded run: token stream bit-exact,
    decision logits to f32 ulp (attention reductions span the bucket width,
    so last-bit equality across widths is not representable)."""
    cfg, params, _ = tiny_trained
    rng = np.random.default_rng(0)
    lens = [15, 20, 9, 20]
    L = max(lens)
    rows = [rng.integers(3, 100, size=ln).astype(np.int32) for ln in lens]
    padded = np.zeros((len(rows), L), np.int32)
    for i, r in enumerate(rows):
        padded[i, : len(r)] = r
    g, d = sampler.generate(params, cfg, padded, max_new_tokens=6,
                            prompt_lens=lens)
    for i, r in enumerate(rows):
        gi, di = sampler.generate(params, cfg, r[None], max_new_tokens=6)
        np.testing.assert_array_equal(g[i], gi[0], err_msg=f"row {i} tokens")
        np.testing.assert_allclose(d[i], di[0], atol=5e-6, rtol=1e-6,
                                   err_msg=f"row {i} decision logits")


def test_generate_full_length_rows_bit_identical_under_lens(tiny_trained):
    """A row whose true length equals the bucket is untouched by the
    per-row machinery: same batch, with vs without prompt_lens."""
    cfg, params, _ = tiny_trained
    prompts = np.random.default_rng(1).integers(
        3, 100, size=(3, 20)).astype(np.int32)
    g0, d0 = sampler.generate(params, cfg, prompts, max_new_tokens=5)
    g1, d1 = sampler.generate(params, cfg, prompts, max_new_tokens=5,
                              prompt_lens=[20, 20, 20])
    np.testing.assert_array_equal(g0, g1)
    np.testing.assert_array_equal(d0, d1)


def test_prompt_lens_validation(tiny_trained):
    cfg, params, _ = tiny_trained
    prompts = np.ones((2, 10), np.int32)
    with pytest.raises(ValueError, match="prompt_lens"):
        sampler.generate(params, cfg, prompts, prompt_lens=[5])
    with pytest.raises(ValueError, match="prompt_lens"):
        sampler.generate(params, cfg, prompts, prompt_lens=[5, 11])
    with pytest.raises(ValueError, match="prompt_lens"):
        sampler.generate(params, cfg, prompts, prompt_lens=[0, 10])


# ---------------------------------------------------------------------------
# DecodeState: chunked segments + slot refill
# ---------------------------------------------------------------------------
def test_decode_segments_match_one_shot(tiny_trained):
    cfg, params, _ = tiny_trained
    prompts = np.random.default_rng(2).integers(
        3, 100, size=(4, 18)).astype(np.int32)
    g1, d1 = sampler.generate(params, cfg, prompts, max_new_tokens=8)
    # warm the per-segment-length executables, then re-run the segment loop
    # under a device->host transfer guard: the hot loop must dispatch with
    # no implicit sync (runtime complement of scopelint's static pass); the
    # np.asarray conversions below are the intended syncs, outside the guard
    warm = sampler.prefill_state(params, cfg, prompts, max_new_tokens=8)
    for steps in (3, 3, 2):
        warm, _, _ = sampler.decode_segment(params, cfg, warm, steps)
    segs = []
    with jax.transfer_guard_device_to_host("disallow"):
        state = sampler.prefill_state(params, cfg, prompts, max_new_tokens=8)
        for steps in (3, 3, 2):
            state, g, d = sampler.decode_segment(params, cfg, state, steps)
            segs.append((g, d))
    gs = [np.asarray(g) for g, _ in segs]
    ds = [np.asarray(d) for _, d in segs]
    np.testing.assert_array_equal(np.concatenate(gs, axis=1), g1)
    np.testing.assert_array_equal(np.concatenate(ds, axis=1), d1)
    assert int(state.positions[0]) == 18 + 8 and state.used == 18 + 8


def test_decode_segments_match_one_shot_temperature(tiny_trained):
    """The sampling key is carried across segments — chunking must not
    change the stochastic stream."""
    cfg, params, _ = tiny_trained
    prompts = np.random.default_rng(3).integers(
        3, 100, size=(3, 16)).astype(np.int32)
    key = jax.random.PRNGKey(7)
    g1, _ = sampler.generate(params, cfg, prompts, max_new_tokens=8,
                             temperature=0.8, rng=key)
    warm = sampler.prefill_state(params, cfg, prompts, max_new_tokens=8,
                                 rng=key)
    for steps in (5, 3):
        warm, _, _ = sampler.decode_segment(params, cfg, warm, steps,
                                            temperature=0.8)
    segs = []
    with jax.transfer_guard_device_to_host("disallow"):
        state = sampler.prefill_state(params, cfg, prompts, max_new_tokens=8,
                                      rng=key)
        for steps in (5, 3):
            state, g, _ = sampler.decode_segment(params, cfg, state, steps,
                                                 temperature=0.8)
            segs.append(g)
    gs = [np.asarray(g) for g in segs]
    np.testing.assert_array_equal(np.concatenate(gs, axis=1), g1)


def _paged_state(params, cfg, prompts, *, budget, lens=None, page_size=8):
    """A paged state over ``prompts``, prefilled, on a pool that holds every
    row's worst case twice over (refilled rows never wait on pages)."""
    from repro.serving.kv_pool import KVPool
    b, width = np.shape(prompts)
    pool = KVPool(n_pages=2 * b * -(-(width + budget) // page_size),
                  page_size=page_size)
    return sampler.prefill_state(params, cfg, prompts, max_new_tokens=budget,
                                 prompt_lens=lens, kv_pool=pool)


def _refill(b, rows, prompts, lens, width):
    """A ``decode_segment`` refill admitting ``prompts`` (their true
    ``lens``) into slot ``rows`` of a b-slot state, padded to ``width``."""
    mask = np.zeros(b, bool)
    mat = np.zeros((b, width), np.int32)
    full = np.ones(b, np.int64)
    for row, p, n in zip(rows, prompts, lens, strict=True):
        mask[row] = True
        mat[row, :n] = np.asarray(p)[:n]
        full[row] = n
    return mask, mat, full


def test_refill_slot_between_segments(tiny_trained):
    """A drained slot refilled with a fresh prompt decodes exactly like a
    standalone run of that prompt, and the other rows are untouched."""
    cfg, params, _ = tiny_trained
    rng = np.random.default_rng(4)
    prompts = rng.integers(3, 100, size=(4, 18)).astype(np.int32)
    state = _paged_state(params, cfg, prompts, budget=8)
    state, _, _ = sampler.decode_segment(params, cfg, state, 4)

    new_prompt = rng.integers(3, 100, size=18).astype(np.int32)
    state, g, d = sampler.decode_segment(
        params, cfg, state, 4, refill=_refill(4, [2], [new_prompt], [18], 18))
    assert int(state.positions[2]) == 18 + 4

    # reference at the same batch size (a b=1 run picks a different gemm
    # path whose accumulation differs in the last ulp): token stream must
    # be bit-exact, decision logits to f32 ulp
    g_ref, d_ref = sampler.generate(params, cfg,
                                    np.repeat(new_prompt[None], 4, 0),
                                    max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(g)[2], g_ref[0])
    np.testing.assert_allclose(np.asarray(d)[2], d_ref[0],
                               atol=5e-6, rtol=1e-6)

    # untouched rows continue bit-identically vs a no-refill run
    s2 = _paged_state(params, cfg, prompts, budget=8)
    s2, _, _ = sampler.decode_segment(params, cfg, s2, 4)
    s2, g2, d2 = sampler.decode_segment(params, cfg, s2, 4)
    np.testing.assert_array_equal(
        np.asarray(g)[[0, 1, 3]], np.asarray(g2)[[0, 1, 3]])
    np.testing.assert_array_equal(
        np.asarray(d)[[0, 1, 3]], np.asarray(d2)[[0, 1, 3]])


def test_refill_slot_padded_prompt_matches_exact(tiny_trained):
    """A refill prompt padded to the warmed bucket width (with its true
    prompt_len) decodes like an exact-length refill: pad garbage in the
    cache tail is masked out by the per-row valid length."""
    cfg, params, _ = tiny_trained
    rng = np.random.default_rng(11)
    prompts = rng.integers(3, 100, size=(4, 18)).astype(np.int32)
    new_prompt = rng.integers(3, 100, size=12).astype(np.int32)
    out = {}
    for width in (12, 18):
        state = _paged_state(params, cfg, prompts, budget=8)
        state, _, _ = sampler.decode_segment(params, cfg, state, 4)
        state, g, d = sampler.decode_segment(
            params, cfg, state, 4,
            refill=_refill(4, [2], [new_prompt], [12], width))
        assert int(state.positions[2]) == 12 + 4
        out[width] = (np.asarray(g), np.asarray(d))
    np.testing.assert_array_equal(out[18][0], out[12][0])
    np.testing.assert_allclose(out[18][1], out[12][1], atol=5e-6, rtol=1e-6)


def test_refill_slots_batched_matches_sequential(tiny_trained):
    """One two-row refill equals two one-row refills in consecutive
    launches: each refilled row decodes the same window from its own
    admission on."""
    cfg, params, _ = tiny_trained
    rng = np.random.default_rng(12)
    prompts = rng.integers(3, 100, size=(4, 18)).astype(np.int32)
    fresh = rng.integers(3, 100, size=(2, 14)).astype(np.int32)

    def run(admissions):
        """Launch one 4-step segment per entry of ``admissions`` (the
        rows refilled by it), then one more; returns (gen, dec) per
        launch."""
        state = _paged_state(params, cfg, prompts, budget=16)
        state, _, _ = sampler.decode_segment(params, cfg, state, 4)
        outs = []
        for rows in admissions + [[]]:
            refill = (_refill(4, rows, [fresh[r // 2] for r in rows],
                              [14] * len(rows), 18) if rows else None)
            state, g, d = sampler.decode_segment(params, cfg, state, 4,
                                                 refill=refill)
            outs.append((np.asarray(g), np.asarray(d)))
        return outs

    batch = run([[1, 3]])
    seq = run([[1], [3]])
    for row, at in ((1, 0), (3, 1)):        # the launch admitting the row
        for k in range(2):
            np.testing.assert_array_equal(seq[at + k][0][row],
                                          batch[k][0][row])
            np.testing.assert_allclose(seq[at + k][1][row],
                                       batch[k][1][row],
                                       atol=5e-6, rtol=1e-6)


def test_decode_segment_refill_guards(tiny_trained):
    cfg, params, _ = tiny_trained
    state = _paged_state(params, cfg, np.ones((2, 10), np.int32), budget=8)
    mat = np.ones((2, 8), np.int32)
    with pytest.raises(ValueError, match="no rows"):
        sampler.decode_segment(params, cfg, state, 4,
                               refill=([False, False], mat, [8, 8]))
    with pytest.raises(ValueError, match="mask/prompts"):
        sampler.decode_segment(params, cfg, state, 4,
                               refill=([True], mat, [8]))
    with pytest.raises(ValueError, match="prompt_lens"):
        sampler.decode_segment(params, cfg, state, 4,
                               refill=([True, False], mat, [0, 8]))
    with pytest.raises(ValueError, match="prompt_lens"):
        sampler.decode_segment(params, cfg, state, 4,
                               refill=([True, False], mat, [9, 8]))


def test_decode_segment_refill_needs_paged_state(tiny_trained):
    """A dense state decodes its one batch to the end: a refill raises."""
    cfg, params, _ = tiny_trained
    state = sampler.prefill_state(params, cfg, np.ones((2, 10), np.int32),
                                  max_new_tokens=8)
    with pytest.raises(ValueError, match="paged state"):
        sampler.decode_segment(params, cfg, state, 4,
                               refill=([True, False], np.ones((2, 8),
                                                              np.int32),
                                       [8, 8]))


@pytest.mark.parametrize("width,length", [(14, 14), (16, 8)])
def test_fused_refill_without_decode_room_raises(tiny_trained, width,
                                                 length):
    """A refill prompt as long as the row capacity (kv_cap = 10 + 4), or
    padded wider than it, leaves no decode room: refused before any page
    moves."""
    cfg, params, _ = tiny_trained
    state = _paged_state(params, cfg, np.ones((2, 10), np.int32), budget=4)
    pool = state.paged.pool
    in_use = pool.pages_in_use
    with pytest.raises(ValueError, match="no decode room"):
        sampler.decode_segment(
            params, cfg, state, 2,
            refill=_refill(2, [0], [[5] * length], [length], width))
    assert pool.pages_in_use == in_use


def test_refill_and_segment_guards(tiny_trained):
    cfg, params, _ = tiny_trained
    prompts = np.ones((2, 10), np.int32)
    state = sampler.prefill_state(params, cfg, prompts, max_new_tokens=4)
    with pytest.raises(ValueError, match="overruns the cache"):
        sampler.decode_segment(params, cfg, state, 5)
    with pytest.raises(ValueError, match="positive"):
        sampler.decode_segment(params, cfg, state, 0)
    # the refill guards, on the paged state a refill needs
    paged = _paged_state(params, cfg, prompts, budget=4)
    with pytest.raises(ValueError, match="no decode room"):
        sampler.decode_segment(params, cfg, paged, 2,
                               refill=_refill(2, [0], [[1] * 14], [14], 14))


def test_generate_requires_rng_for_stochastic_decoding(tiny_trained):
    """temperature>0 without an explicit key must raise: the old
    PRNGKey(0) fallback sampled the identical stream on every call."""
    cfg, params, _ = tiny_trained
    prompts = np.ones((1, 8), np.int32)
    with pytest.raises(ValueError, match="rng"):
        sampler.generate(params, cfg, prompts, max_new_tokens=2,
                         temperature=0.7)
    # greedy keeps its deterministic no-key default
    g1, _ = sampler.generate(params, cfg, prompts, max_new_tokens=2)
    g2, _ = sampler.generate(params, cfg, prompts, max_new_tokens=2)
    np.testing.assert_array_equal(g1, g2)


def test_estimator_batch_requires_rng_for_stochastic(tiny_trained):
    cfg, params, _ = tiny_trained
    est = ReasoningEstimator(cfg, params, max_new_tokens=4)
    prompts = [[5] * 12, [6] * 12]
    with pytest.raises(ValueError, match="rng"):
        est.predict_batch(prompts, temperature=0.9)
    out = est.predict_batch(prompts, temperature=0.9,
                            rng=jax.random.PRNGKey(3))
    assert len(out) == 2


def test_dispatch_batch_empty_returns_empty_parse(tiny_trained):
    cfg, params, _ = tiny_trained
    est = ReasoningEstimator(cfg, params, max_new_tokens=4)
    handle = est.dispatch_batch([])
    assert handle.is_ready()
    assert len(handle.parse()) == 0        # not a concatenate crash


def test_ragged_prompt_lens_rejected_for_ssm_backbone():
    """SSM/conv prefill state consumes right-pad tokens (no per-row
    masking), so sub-bucket lengths must fail loudly, not corrupt."""
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config("mamba2-1.3b").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = np.ones((2, 10), np.int32)
    with pytest.raises(ValueError, match="attention-only"):
        sampler.prefill_state(params, cfg, prompts, max_new_tokens=2,
                              prompt_lens=[6, 10])
    # full-length rows carry no pad into the state: still allowed
    sampler.prefill_state(params, cfg, prompts, max_new_tokens=2,
                          prompt_lens=[10, 10])


# ---------------------------------------------------------------------------
# ServeRuntime: FIFO parse order, capacity, sync/overlap paths
# ---------------------------------------------------------------------------
class _Handle:
    def __init__(self, name, ready, log):
        self.name = name
        self._ready = ready
        self.log = log

    def is_ready(self):
        return self._ready

    def parse(self):
        self.log.append(("parse", self.name))
        return self.name


def _mb(name):
    return Microbatch(np.zeros((1, 4), np.int32), [name],
                      np.full((1,), 4, np.int32), (1, 4))


def test_serve_runtime_fifo_and_capacity():
    log, parsed = [], []

    def dispatch(mb):
        log.append(("dispatch", mb.tags[0]))
        return _Handle(mb.tags[0], ready=False, log=log)

    rt = ServeRuntime(dispatch, on_parsed=lambda mb, r: parsed.append(r),
                      max_pending=1)
    rt.dispatch([_mb("a")])
    assert log == [("dispatch", "a")] and len(rt) == 1
    rt.dispatch([_mb("b")])            # capacity: parse a BEFORE launching b
    assert log == [("dispatch", "a"), ("parse", "a"), ("dispatch", "b")]
    assert parsed == ["a"] and len(rt) == 1
    rt.finish()
    assert parsed == ["a", "b"] and len(rt) == 0
    assert rt.stats.dispatched == 2 and rt.stats.parsed == 2


def test_serve_runtime_sync_mode_parses_immediately():
    log, parsed = [], []
    rt = ServeRuntime(
        lambda mb: _Handle(mb.tags[0], ready=True, log=log),
        on_parsed=lambda mb, r: parsed.append(r), max_pending=0)
    rt.dispatch([_mb("a"), _mb("b")])
    assert parsed == ["a", "b"] and len(rt) == 0


def test_serve_runtime_poll_parses_only_ready():
    log, parsed = [], []
    handles = {}

    def dispatch(mb):
        h = _Handle(mb.tags[0], ready=False, log=log)
        handles[mb.tags[0]] = h
        return h

    rt = ServeRuntime(dispatch, on_parsed=lambda mb, r: parsed.append(r),
                      max_pending=2)
    rt.dispatch([_mb("a"), _mb("b")])
    assert rt.poll() == 0 and parsed == []
    handles["b"]._ready = True         # b done, but a (older) still running:
    assert rt.poll() == 0              # FIFO order is never violated
    handles["a"]._ready = True
    assert rt.poll() == 2 and parsed == ["a", "b"]
    # duck-typed results (no is_ready/parse) degrade to the sync path
    rt2 = ServeRuntime(lambda mb: mb.tags[0],
                       on_parsed=lambda mb, r: parsed.append(r),
                       max_pending=0)
    rt2.dispatch([_mb("c")])
    assert parsed[-1] == "c"


# ---------------------------------------------------------------------------
# Engine: overlapped stream parity + ragged length-grid parity
# ---------------------------------------------------------------------------
@pytest.fixture()
def real_engine(tiny_trained, world, retriever, library):
    cfg, params, _ = tiny_trained
    data = build_scope_data(world, n_queries=160, seed=9)

    def mk(**kw):
        return ScopeEngine.build(EngineConfig(
            estimator=ReasoningEstimator(cfg, params, max_new_tokens=6),
            retriever=retriever, library=library,
            models_meta={m: world.models[m] for m in data.models}, **kw))
    return mk, data


def test_stream_overlap_modes_bit_identical(real_engine):
    """Overlap changes when the host blocks, never what it observes: the
    double-buffered and synchronous streams see the same microbatches and
    must agree bit-for-bit; both match batch ``predict`` decisions (same
    tokens; confidences to f32 ulp — the one-big-batch shape reduces in a
    different order on this backend)."""
    mk, data = real_engine
    queries = [data.queries[int(q)] for q in data.test_qids[:6]]
    ticks = [queries[:2], queries[2:3], queries[3:6]]
    ref = mk().predict(RouteRequest(queries))

    got = {}
    for overlap in (True, False):
        sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
        pools = list(mk().predict_stream(
            (RouteRequest(t) for t in ticks), scheduler=sched,
            overlap=overlap))
        got[overlap] = (np.concatenate([p.p_hat for p in pools]),
                        np.concatenate([p.y_hat for p in pools]))
    np.testing.assert_array_equal(got[True][0], got[False][0])
    np.testing.assert_array_equal(got[True][1], got[False][1])
    np.testing.assert_array_equal(got[True][1], ref.y_hat)
    np.testing.assert_allclose(got[True][0], ref.p_hat,
                               atol=1e-6, rtol=1e-6)


def test_stream_length_grid_matches_exact_fit(real_engine):
    """Ragged lengths under a configured prompt_lens grid: sub-bucket rows
    ride padded buckets yet the decisions match the unpadded exact-fit
    path — token-derived fields exactly, confidence to f32 ulp."""
    mk, data = real_engine
    queries = [data.queries[int(q)] for q in data.test_qids[:5]]
    ticks = [queries[:2], queries[2:5]]
    ref = mk().predict(RouteRequest(queries))

    prompt_len = len(mk()._prepare(RouteRequest(queries[:1]), False)
                     .prompts[0])
    grid = BucketConfig(batch_sizes=(1, 2, 4, 8),
                        prompt_lens=(prompt_len + 7,))
    sched = MicrobatchScheduler(grid)
    pools = list(mk().predict_stream((RouteRequest(t) for t in ticks),
                                     scheduler=sched))
    assert sched.stats.pad_tokens > 0          # the grid really padded
    y = np.concatenate([p.y_hat for p in pools])
    lh = np.concatenate([p.len_hat for p in pools])
    wf = np.concatenate([p.well_formed for p in pools])
    cost = np.concatenate([p.cost_hat for p in pools])
    p_hat = np.concatenate([p.p_hat for p in pools])
    np.testing.assert_array_equal(y, ref.y_hat)
    np.testing.assert_array_equal(lh, ref.len_hat)
    np.testing.assert_array_equal(wf, ref.well_formed)
    np.testing.assert_array_equal(cost, ref.cost_hat)   # true prompt lens
    np.testing.assert_allclose(p_hat, ref.p_hat, atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# Per-row window parse: refilled rows start mid-buffer
# ---------------------------------------------------------------------------
def test_parse_generations_windows_match_gathered():
    """Windowed parse == plain parse of the hand-gathered windows, over a
    buffer whose rows sit at different offsets with different lengths."""
    rng = np.random.default_rng(5)
    T, N = 24, 6
    gen = rng.integers(0, 40, size=(N, T))
    dec = rng.normal(size=(N, T, 2))
    # plant a well-formed body at each row's own offset
    starts = np.array([0, 3, 8, 0, 15, 20])
    lens = np.array([6, 6, 6, 4, 6, 4])
    for i, s in enumerate(starts):
        gen[i, s: s + 3] = [tok.YES, tok.LEN_BASE + 2, tok.EOS]
        gen[i, s + 3: s + lens[i]] = tok.PAD
    ref_rows = []
    for i in range(N):
        w = gen[i, starts[i]: starts[i] + lens[i]]
        dw = dec[i, starts[i]: starts[i] + lens[i]]
        pad = np.full(int(lens.max()) - lens[i], tok.PAD)
        ref_rows.append(parse_generations(
            np.concatenate([w, pad])[None],
            np.concatenate([dw, np.zeros((len(pad), 2))])[None]))
    got = parse_generations(gen, dec, starts=starts, lens=lens)
    for i, ref in enumerate(ref_rows):
        assert got.y_hat[i] == ref.y_hat[0]
        assert got.len_hat[i] == ref.len_hat[0]
        assert got.well_formed[i] == ref.well_formed[0]
        assert got.pred_tokens[i] == ref.pred_tokens[0]
        np.testing.assert_allclose(got.p_conf[i], ref.p_conf[0])


def test_parse_generations_window_validation():
    gen = np.zeros((2, 8), int)
    dec = np.zeros((2, 8, 2))
    with pytest.raises(ValueError, match="inside"):
        parse_generations(gen, dec, starts=[0, 6], lens=[8, 4])
    with pytest.raises(ValueError, match="must be"):
        parse_generations(gen, dec, starts=[0], lens=[4, 4])


def test_decode_handle_windows(tiny_trained):
    """DecodeHandle.parse with windows == parsing each row's slice."""
    cfg, params, _ = tiny_trained
    prompts = np.random.default_rng(6).integers(
        3, 100, size=(3, 16)).astype(np.int32)
    g, d = sampler.generate(params, cfg, prompts, max_new_tokens=8)
    windows = [(0, 8), (2, 6), (4, 4)]
    got = DecodeHandle([(g, d)], windows=windows).parse()
    for i, (s, ln) in enumerate(windows):
        pad = 8 - ln
        ref = parse_generations(
            np.concatenate([g[i, s: s + ln], np.full(pad, tok.PAD)])[None],
            np.concatenate([d[i, s: s + ln], np.zeros((pad, 2))])[None])
        assert got.y_hat[i] == ref.y_hat[0]
        assert got.pred_tokens[i] == ref.pred_tokens[0]
        np.testing.assert_allclose(got.p_conf[i], ref.p_conf[0])


# ---------------------------------------------------------------------------
# SlotRun: segment-chunked decode with mid-batch refill
# ---------------------------------------------------------------------------
def _drive_slot_run(est, prompts, tags, extra, *, segment_len):
    """Step a SlotRun to completion, admitting ``extra`` = [(tag, prompt)]
    into slots as they drain; returns {tag: per-field dict}."""
    run = est.open_slots(np.asarray(prompts, np.int32), tags=list(tags),
                         segment_len=segment_len)
    queue = list(extra)
    results = {}
    while not run.finished or queue:
        if queue and run.free_rows() and run.can_admit():
            n = min(len(queue), len(run.free_rows()))
            run.admit([(t, p, len(p)) for t, p in queue[:n]])
            del queue[:n]
        assert not run.finished, "queue left but the run finished"
        tags_done, batch = run.step()
        for i, t in enumerate(tags_done):
            results[t] = {f: getattr(batch, f)[i] for f in
                          ("y_hat", "len_hat", "well_formed", "p_conf",
                           "pred_tokens", "rationale_len")}
    return results, run


def test_slot_run_refilled_rows_match_standalone(tiny_trained):
    """Every request served through a SlotRun — original rows and
    mid-batch refills alike — parses identically to a standalone
    whole-batch run of the same prompts."""
    cfg, params, _ = tiny_trained
    est = ReasoningEstimator(cfg, params, max_new_tokens=8)
    rng = np.random.default_rng(7)
    prompts = rng.integers(3, 100, size=(4, 18)).astype(np.int32)
    extra = rng.integers(3, 100, size=(3, 18)).astype(np.int32)

    results, run = _drive_slot_run(
        est, prompts, tags=["a", "b", "c", "d"],
        extra=[("e", list(extra[0])), ("f", list(extra[1])),
               ("g", list(extra[2]))], segment_len=4)
    assert set(results) == set("abcdefg")
    assert run.slot_steps_total > 0
    assert run.refill_steps > 0

    ref = est.predict_batch(
        [list(p) for p in np.concatenate([prompts, extra])])
    for i, t in enumerate("abcdefg"):
        got = results[t]
        assert got["y_hat"] == ref.y_hat[i], t
        assert got["len_hat"] == ref.len_hat[i], t
        assert got["well_formed"] == ref.well_formed[i], t
        assert got["pred_tokens"] == ref.pred_tokens[i], t
        assert got["rationale_len"] == ref.rationale_len[i], t
        np.testing.assert_allclose(got["p_conf"], ref.p_conf[i],
                                   atol=1e-6, rtol=1e-6, err_msg=t)


def test_slot_run_partial_bucket_has_free_slots(tiny_trained):
    """Rows beyond the real tags of a partially-filled opening bucket are
    immediately-free slots — a refill target from boundary zero."""
    cfg, params, _ = tiny_trained
    est = ReasoningEstimator(cfg, params, max_new_tokens=8)
    prompts = np.random.default_rng(8).integers(
        3, 100, size=(4, 12)).astype(np.int32)
    run = est.open_slots(prompts, tags=["a", "b"], segment_len=4)
    assert run.free_rows() == [2, 3]
    assert run.n_live == 2 and run.can_admit()


def test_slot_run_guards(tiny_trained):
    cfg, params, _ = tiny_trained
    est = ReasoningEstimator(cfg, params, max_new_tokens=8)
    prompts = np.ones((2, 10), np.int32)
    with pytest.raises(ValueError, match="segment_len"):
        est.open_slots(prompts, segment_len=0)
    with pytest.raises(ValueError, match="segment_len"):
        est.open_slots(prompts, segment_len=99)
    run = est.open_slots(prompts, segment_len=4)
    with pytest.raises(ValueError, match="free slots"):
        run.admit([("x", [1] * 5, 5)])
    with pytest.raises(ValueError, match="tags"):
        est.open_slots(prompts, tags=["a", "b", "c"], segment_len=4)


# ---------------------------------------------------------------------------
# Engine: segment-chunked refill stream
# ---------------------------------------------------------------------------
def test_stream_refill_matches_whole_retire(real_engine):
    """Refill-on and refill-off streams make identical routing decisions:
    token-derived fields bit-equal, confidences to f32 ulp (partial
    buckets run a different executable shape in whole-retire mode), and
    both match batch ``predict``."""
    mk, data = real_engine
    queries = [data.queries[int(q)] for q in data.test_qids[:7]]
    ticks = [queries[:2], queries[2:3], queries[3:7]]
    ref = mk().predict(RouteRequest(queries))

    pools, scheds = {}, {}
    for refill in (False, True):
        sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
        pools[refill] = list(mk().predict_stream(
            (RouteRequest(t) for t in ticks), scheduler=sched,
            refill=refill, segment_len=3))
        scheds[refill] = sched
    assert len(pools[True]) == len(ticks)
    for field in ("y_hat", "len_hat", "well_formed", "cost_hat",
                  "pred_overhead"):
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(getattr(p, field)) for p in
                            pools[True]]),
            np.concatenate([np.asarray(getattr(p, field)) for p in
                            pools[False]]), err_msg=field)
    np.testing.assert_allclose(
        np.concatenate([p.p_hat for p in pools[True]]),
        np.concatenate([p.p_hat for p in pools[False]]),
        atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(
        np.concatenate([p.y_hat for p in pools[True]]), ref.y_hat)
    # both modes account decode-slot occupancy in SchedulerStats
    for refill in (False, True):
        st = scheds[refill].stats
        assert st.slot_steps_total > 0
        assert 0.0 < st.slot_occupancy <= 1.0
    # every scheduled prompt was delivered exactly once
    assert scheds[True].stats.emitted == scheds[True].stats.submitted


def test_stream_refill_cache_and_dedup(real_engine):
    """Cache writes land per parse group and in-flight duplicates share
    generations in refill mode, exactly as in the whole-retire stream."""
    mk, data = real_engine
    queries = [data.queries[int(q)] for q in data.test_qids[:4]]
    ticks = [queries[:2], queries[:2], queries[2:4]]
    engine = mk()
    pools = list(engine.predict_stream(
        (RouteRequest(t) for t in ticks),
        scheduler=MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8))),
        refill=True, segment_len=3))
    # the duplicated middle tick spends no new estimator tokens
    assert int(pools[1].pred_overhead.sum()) == 0
    np.testing.assert_array_equal(pools[1].y_hat, pools[0].y_hat)
    # a later identical request is served from the cache, zero decode
    again = list(engine.predict_stream(
        iter([RouteRequest(queries[:2])]), refill=True))
    assert again[0].cache_hits == again[0].y_hat.size
    np.testing.assert_array_equal(again[0].y_hat, pools[0].y_hat)


def test_stream_refill_requires_slot_estimator(real_engine):
    """refill=True with an estimator lacking open_slots fails loudly."""
    mk, data = real_engine

    class Duck:
        def predict(self, prompts, rng=None):
            raise AssertionError("unreachable")

    engine = mk()
    engine.set_estimator(Duck(), "duck-v1")
    with pytest.raises(TypeError, match="open_slots"):
        list(engine.predict_stream(
            iter([RouteRequest([data.queries[int(data.test_qids[0])]])]),
            refill=True))


def test_stream_deadline_flush_bounds_queue_age(real_engine):
    """A fake clock drives the deadline: the lone first-tick query ships in
    a partially-filled bucket once max_queue_age expires instead of waiting
    for the stream to end."""
    mk, data = real_engine
    queries = [data.queries[int(q)] for q in data.test_qids[:4]]
    now = [0.0]
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(64,)),
                                max_queue_age=1.0, clock=lambda: now[0])

    def ticks():
        yield RouteRequest(queries[:1])
        now[0] += 2.0                   # deadline expires between ticks
        yield RouteRequest(queries[1:])

    engine = mk()
    pools = list(engine.predict_stream(ticks(), scheduler=sched))
    assert sched.stats.deadline_flushes > 0
    assert sched.stats.partial_microbatches > 0
    ref = mk().predict(RouteRequest(queries))
    np.testing.assert_array_equal(
        np.concatenate([p.y_hat for p in pools]), ref.y_hat)
    np.testing.assert_allclose(
        np.concatenate([p.p_hat for p in pools]), ref.p_hat,
        atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# Paged KV cache: sampler-level parity vs the dense oracle
# ---------------------------------------------------------------------------
def _paged_pair(cfg, params, prompts, lens, *, budget, pool_pages=64,
                page_size=8, kernel=None):
    """(dense state, paged state) over the same prompts — the paged kv_cap
    equals the dense cache width, so the XLA paged path is bit-identical
    by construction (gather -> slice -> the dense kernel)."""
    from repro.kernels.decode_attention import KernelType
    from repro.serving.kv_pool import KVPool
    dense = sampler.prefill_state(params, cfg, prompts,
                                  max_new_tokens=budget, prompt_lens=lens)
    pool = KVPool(n_pages=pool_pages, page_size=page_size)
    paged = sampler.prefill_state(params, cfg, prompts,
                                  max_new_tokens=budget, prompt_lens=lens,
                                  kv_pool=pool,
                                  kv_kernel=kernel or KernelType.XLA)
    return dense, paged, pool


def test_paged_prefill_and_segments_bit_identical(tiny_trained):
    """XLA paged decode == dense decode, bit for bit: ragged prompt lens,
    multiple scan segments, per-row positions."""
    cfg, params, _ = tiny_trained
    rng = np.random.default_rng(10)
    prompts = rng.integers(3, 100, size=(3, 20)).astype(np.int32)
    lens = [20, 13, 7]
    dense, paged, _ = _paged_pair(cfg, params, prompts, lens, budget=12)
    np.testing.assert_array_equal(np.asarray(dense.last_logits),
                                  np.asarray(paged.last_logits))
    for steps in (5, 4, 3):
        dense, g0, d0 = sampler.decode_segment(params, cfg, dense, steps)
        paged, g1, d1 = sampler.decode_segment(params, cfg, paged, steps)
        np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(dense.positions),
                                  np.asarray(paged.positions))
    np.testing.assert_array_equal(np.asarray(dense.done),
                                  np.asarray(paged.done))


def test_paged_refill_segment_bit_identical(tiny_trained):
    """The fused refill+decode executable under paging: the refilled row
    restarts from its true length in fresh pages and decodes as
    ``generate`` does for its prompt; the untouched rows keep decoding
    bit-identically to a paged run with no refill."""
    cfg, params, _ = tiny_trained
    rng = np.random.default_rng(11)
    prompts = rng.integers(3, 100, size=(3, 16)).astype(np.int32)
    fresh = rng.integers(3, 100, size=(16,)).astype(np.int32)
    lens = [16, 11, 16]
    outs = {}
    for refill in (False, True):
        state = _paged_state(params, cfg, prompts, budget=14, lens=lens)
        state, _, _ = sampler.decode_segment(params, cfg, state, 4)
        state, g, d = sampler.decode_segment(
            params, cfg, state, 4,
            refill=_refill(3, [1], [fresh], [12], 16) if refill else None)
        outs[refill] = (np.asarray(g), np.asarray(d))
    keep = [0, 2]
    np.testing.assert_array_equal(outs[True][0][keep], outs[False][0][keep])
    np.testing.assert_array_equal(outs[True][1][keep], outs[False][1][keep])
    g_ref, d_ref = sampler.generate(params, cfg,
                                    np.repeat(fresh[None, :12], 3, 0),
                                    max_new_tokens=4)
    np.testing.assert_array_equal(outs[True][0][1], g_ref[0])
    np.testing.assert_allclose(outs[True][1][1], d_ref[0],
                               atol=5e-6, rtol=1e-6)


def test_paged_pallas_kernel_matches_dense(tiny_trained):
    """The Pallas paged kernel (interpret mode on CPU) reproduces the dense
    token stream exactly; logits agree to kernel tolerance."""
    cfg, params, _ = tiny_trained
    from repro.kernels.decode_attention import KernelType
    rng = np.random.default_rng(13)
    prompts = rng.integers(3, 100, size=(3, 20)).astype(np.int32)
    dense, paged, _ = _paged_pair(cfg, params, prompts, [20, 13, 7],
                                  budget=10, kernel=KernelType.PALLAS)
    for steps in (5, 5):
        dense, g0, d0 = sampler.decode_segment(params, cfg, dense, steps)
        paged, g1, d1 = sampler.decode_segment(params, cfg, paged, steps)
        np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
        np.testing.assert_allclose(np.asarray(d0), np.asarray(d1),
                                   atol=2e-5, rtol=2e-5)


def test_paged_pool_accounting_and_release(tiny_trained):
    """Pages flow free-list -> rows -> free-list: prompt pages allocated at
    admission, decode pages drawn from the row's reservation per segment,
    everything returned on retire; peaks track live tokens, not slots."""
    cfg, params, _ = tiny_trained
    from repro.serving.kv_pool import KVPool
    prompts = np.random.default_rng(14).integers(
        3, 100, size=(2, 16)).astype(np.int32)
    pool = KVPool(n_pages=12, page_size=8)
    state = sampler.prefill_state(params, cfg, prompts, max_new_tokens=8,
                                  kv_pool=pool)
    # 16-token prompts: 2 pages allocated, 3 reserved (24-token worst case)
    assert pool.pages_in_use == 4 and pool.reserved == 2
    assert pool.live_tokens == 32
    state, _, _ = sampler.decode_segment(params, cfg, state, 8)
    assert pool.pages_in_use == 6 and pool.reserved == 0
    assert pool.live_tokens == 48 and pool.tokens_peak == 48
    pg = state.paged
    pg.retire_row(0)
    pg.retire_row(1)
    assert pool.pages_in_use == 0 and pool.available() == 12
    assert pool.live_tokens == 0 and pool.tokens_peak == 48
    assert (pg.table == pool.trash_page).all()


def test_paged_guards(tiny_trained):
    cfg, params, _ = tiny_trained
    from repro.serving.kv_pool import KVPool, check_paged_support
    prompts = np.random.default_rng(15).integers(
        3, 100, size=(2, 10)).astype(np.int32)
    # a pool too small for even one full-budget row fails loudly at admit
    with pytest.raises(ValueError, match="full-budget row"):
        sampler.prefill_state(params, cfg, prompts, max_new_tokens=64,
                              kv_pool=KVPool(n_pages=4, page_size=8))
    # page size wider than the whole cache is a config error
    with pytest.raises(ValueError, match="kv_page_size"):
        sampler.prefill_state(params, cfg, prompts, max_new_tokens=4,
                              kv_pool=KVPool(n_pages=8, page_size=64))
    # decoding past a row's kv_cap is caught host-side before the launch
    pool = KVPool(n_pages=16, page_size=8)
    state = sampler.prefill_state(params, cfg, prompts, max_new_tokens=6,
                                  kv_pool=pool)
    with pytest.raises(ValueError, match="paged row"):
        sampler.decode_segment(params, cfg, state, 7)
    # backbones with no paged layout (SSM states, sliding windows) are
    # rejected up front; MLA latents have one
    from repro.configs import get_config
    with pytest.raises(ValueError, match="paged"):
        check_paged_support(get_config("mamba2-1.3b").reduced())
    with pytest.raises(ValueError, match="paged"):
        check_paged_support(get_config("gemma2-2b").reduced())
    check_paged_support(get_config("deepseek-v2-lite-16b").reduced())


def test_slot_run_paged_admission_gates_on_pages(tiny_trained):
    """can_admit() reflects the page pool: a pool
    sized for the opening rows only defers further admissions until a row
    retires and frees its pages."""
    cfg, params, _ = tiny_trained
    from repro.serving.kv_pool import KVPool
    est = ReasoningEstimator(cfg, params, max_new_tokens=8)
    prompts = np.random.default_rng(17).integers(
        3, 100, size=(3, 16)).astype(np.int32)
    # exactly two worst-case rows: ceil((16+8)/8) = 3 pages each
    pool = KVPool(n_pages=6, page_size=8)
    run = est.open_slots(prompts, tags=["a"], kv_pool=pool, segment_len=4)
    assert run.kv_pool is pool and run.state.paged.pool is pool
    # rows 1-2 are free, but the live row's reservation leaves only 3
    # pages — one more worst-case row: admit it, then the pool is dry
    # even though a free slot remains
    assert run.can_admit()
    run.admit([("b", [5] * 10, 10)])
    assert not run.can_admit() and run.free_rows() == [2]
    with pytest.raises(ValueError, match="no room"):
        run.admit([("c", [5] * 4, 4)])
    while not run.finished:
        run.step()
    assert pool.pages_in_use == 0 and pool.reserved == 0


# ---------------------------------------------------------------------------
# Row buckets: the paged prefills compute the admitted rows only
# ---------------------------------------------------------------------------
def test_row_bucket_rule():
    """The smallest of b/4, b/2 and b that holds the admitted rows; a full
    admission takes the whole batch; a sharded batch keeps buckets that
    are a multiple of its sharding degree; filler entries equal b."""
    assert sampler.row_buckets(64) == (16, 32, 64)
    for n, want in [(1, 16), (16, 16), (17, 32), (32, 32), (33, 64),
                    (64, 64)]:
        rows = sampler.bucket_rows(np.arange(n), 64)
        assert len(rows) == want, n
        assert rows[:n].tolist() == list(range(n))
        assert (rows[n:] == 64).all()
    assert sampler.row_buckets(8) == (2, 4, 8)
    assert sampler.row_buckets(8, 4) == (4, 8)
    assert sampler.row_buckets(4, 4) == (4,)
    assert sampler.row_buckets(3) == (1, 3)
    assert sampler.bucket_rows([6, 1, 3], 8).tolist() == [6, 1, 3, 8]


def _pool(b, width, page_size=8, budget=12):
    from repro.serving.kv_pool import KVPool
    return KVPool(n_pages=b * -(-(width + budget) // page_size),
                  page_size=page_size)


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8])     # 1, b/4, b/4+1, b/2+1, b
def test_row_bucket_prefills_match_slot_aligned(tiny_trained, n):
    """Opening a state whose first launch admits n of b = 8 slots, and a
    fused refill launch admitting n slots of a full state, serve the same
    tokens as today's slot-aligned computation, which prefills all b rows.
    Decision logits are bit-identical where the bucket is the whole batch,
    and within 1e-6 (absolute and relative) where it is smaller: the CPU
    backend blocks a matrix product by its row count, so a smaller batch
    rounds its sums in another order."""
    cfg, params, _ = tiny_trained
    b, width = 8, 16
    rng = np.random.default_rng(40 + n)
    prompts = rng.integers(3, 100, size=(b, width)).astype(np.int32)
    lens = rng.integers(width // 2, width + 1, size=b)
    admitted = np.zeros(b, bool)
    admitted[rng.choice(b, n, replace=False)] = True
    bucket = min(r for r in (2, 4, 8) if r >= n)
    if bucket == b:
        close = np.testing.assert_array_equal
    else:
        close = functools.partial(np.testing.assert_allclose, atol=1e-6,
                                  rtol=1e-6)

    # opening: nothing prefilled, the admitted rows ride the first launch;
    # against a prefill of all b rows and a plain segment
    got = sampler.open_state(params, cfg, prompts, max_new_tokens=12,
                             kv_pool=_pool(b, width))
    assert got.prefill_rows == 0
    got, g1, d1 = sampler.decode_segment(params, cfg, got, 4,
                                         refill=(admitted, prompts, lens))
    want = sampler.prefill_state(params, cfg, prompts, max_new_tokens=12,
                                 prompt_lens=lens, kv_pool=_pool(b, width))
    assert (got.prefill_rows, want.prefill_rows) == (bucket, b)
    want, g2, d2 = sampler.decode_segment(params, cfg, want, 4)
    np.testing.assert_array_equal(np.asarray(g1)[admitted],
                                  np.asarray(g2)[admitted])
    close(np.asarray(d1)[admitted], np.asarray(d2)[admitted])
    np.testing.assert_array_equal(np.asarray(got.positions)[admitted],
                                  np.asarray(want.positions)[admitted])

    # fused refill of the same slots on a full state: the admitted rows
    # against a prefill of all b fresh prompts and a plain segment, the
    # others against the full state decoding on with no refill
    fresh = rng.integers(3, 100, size=(b, width)).astype(np.int32)
    flens = rng.integers(width // 2, width + 1, size=b)
    states = []
    for _ in range(2):
        st = sampler.prefill_state(params, cfg, prompts, max_new_tokens=12,
                                   prompt_lens=lens, kv_pool=_pool(b, width))
        st, _, _ = sampler.decode_segment(params, cfg, st, 4)
        states.append(st)
    fused, g1, d1 = sampler.decode_segment(
        params, cfg, states[0], 4, refill=(admitted, fresh, flens))
    assert fused.prefill_rows == bucket
    ref = sampler.prefill_state(params, cfg, fresh, max_new_tokens=12,
                                prompt_lens=flens, kv_pool=_pool(b, width))
    ref, g2, d2 = sampler.decode_segment(params, cfg, ref, 4)
    np.testing.assert_array_equal(np.asarray(g1)[admitted],
                                  np.asarray(g2)[admitted])
    close(np.asarray(d1)[admitted], np.asarray(d2)[admitted])
    np.testing.assert_array_equal(np.asarray(fused.positions)[admitted],
                                  np.asarray(ref.positions)[admitted])
    rest, g3, d3 = sampler.decode_segment(params, cfg, states[1], 4)
    np.testing.assert_array_equal(np.asarray(g1)[~admitted],
                                  np.asarray(g3)[~admitted])
    close(np.asarray(d1)[~admitted], np.asarray(d3)[~admitted])
    np.testing.assert_array_equal(np.asarray(fused.positions)[~admitted],
                                  np.asarray(rest.positions)[~admitted])


def test_row_buckets_warm_at_first_open(tiny_trained):
    """The first paged open runs every row bucket of the fused refill;
    after it, opens and refills that hit every bucket compile nothing,
    and the counters record each launch's bucket."""
    from repro.serving.scheduler import SchedulerStats
    cfg, params, _ = tiny_trained
    b, width, budget = 8, 22, 8          # a width no other test compiles
    est = ReasoningEstimator(cfg, params, max_new_tokens=budget)
    pool = _pool(b, width, budget=budget)
    rng = np.random.default_rng(50)
    prompts = rng.integers(3, 100, size=(b, width)).astype(np.int32)
    before = dict(sampler.COMPILE_COUNTS)
    run = est.open_slots(prompts, tags=["o"], kv_pool=pool, segment_len=4)
    warm = {k: v - before.get(k, 0) for k, v in sampler.COMPILE_COUNTS.items()
            if v != before.get(k, 0)}
    # one refill executable per bucket (the storage one may be shared)
    assert warm["paged_refill_scan_decode"] == 3
    assert set(warm) <= {"paged_open", "paged_refill_scan_decode"}
    after_open = dict(sampler.COMPILE_COUNTS)

    def prompt():
        return list(rng.integers(3, 100, size=int(rng.integers(8, width + 1))))

    served = len(run.step()[0])          # the opening row: bucket 2
    for k in (2, 3, 6, 1):               # buckets 2, 4, 8, 2
        while len(run.free_rows()) < k:
            served += len(run.step()[0])
        run.admit([(f"{k}.{i}", p, len(p))
                   for i, p in enumerate(prompt() for _ in range(k))])
        served += len(run.step()[0])
    while not run.finished:
        served += len(run.step()[0])
    assert served == 1 + 2 + 3 + 6 + 1
    assert run.prefill_launches_by_rows == {2: 3, 4: 1, 8: 1}
    assert run.prefill_rows == 2 * 3 + 4 + 8
    stats = SchedulerStats()
    run.account(stats)
    assert stats.prefill_launches_by_rows == {2: 3, 4: 1, 8: 1}
    assert stats.prefill_rows == run.prefill_rows
    for n in (3, 5, 8):                  # opens at buckets 4, 8, 8
        run = est.open_slots(prompts, tags=list(range(n)), kv_pool=pool,
                             segment_len=4)
        while not run.finished:
            run.step()
        assert run.prefill_launches_by_rows[min(r for r in (2, 4, 8)
                                                if r >= n)] == 1
    # (the plain segment compiles at its first use, as without buckets)
    opened = ("paged_open", "paged_refill_scan_decode")
    assert {k: sampler.COMPILE_COUNTS[k] for k in opened} == \
        {k: after_open[k] for k in opened}


# ---------------------------------------------------------------------------
# One slot layout: a continuous-batching state is always paged
# ---------------------------------------------------------------------------
def test_open_slots_default_pool_is_engine_worst_case(real_engine):
    """open_slots() with no pool pages the slots over the pool the engine
    builds when kv_pool_pages is None: every slot's prompt width plus its
    budget steps, page-rounded, at the default page size of 16."""
    from repro.serving.kv_pool import worst_case_pool
    mk, data = real_engine
    engine = mk(refill=True)
    est = engine.estimator
    opened = []

    def open_slots(tokens, **kw):
        run = type(est).open_slots(est, tokens, **kw)
        opened.append((np.shape(tokens), kw["kv_pool"], run))
        return run

    est.open_slots = open_slots
    queries = [data.queries[int(q)] for q in data.test_qids[:2]]
    list(engine.predict_stream(
        iter([RouteRequest(queries)]), segment_len=4,
        scheduler=MicrobatchScheduler(BucketConfig(batch_sizes=(8,)))))
    assert opened
    budget_steps = -(-est.max_new_tokens // 4) * 4
    for (b, width), pool, run in opened:
        want = worst_case_pool(b, width, budget_steps)
        assert (pool.n_pages, pool.page_size) == (want.n_pages, 16)
        assert pool.n_pages == b * -(-(width + budget_steps) // 16)
        own = type(est).open_slots(est, np.ones((b, width), np.int32),
                                   segment_len=4)
        assert (own.kv_pool.n_pages, own.kv_pool.page_size) == \
            (pool.n_pages, pool.page_size)
        assert own.state.paged is not None and run.state.paged is not None


@pytest.mark.parametrize("horizon", [None, 16])
def test_slot_run_horizon_must_be_none(tiny_trained, horizon):
    """A slot state has no dense horizon: the keyword stays for callers
    that pass it, and only None is accepted."""
    from repro.core.estimator import SlotRun
    cfg, params, _ = tiny_trained
    est = ReasoningEstimator(cfg, params, max_new_tokens=8)
    prompts = np.ones((2, 10), np.int32)
    if horizon is None:
        run = SlotRun(est, prompts, tags=["a"], segment_len=4,
                      horizon=horizon)
        assert run.kv_pool.page_size == 16 and run.can_admit()
    else:
        with pytest.raises(ValueError, match="horizon"):
            SlotRun(est, prompts, segment_len=4, horizon=horizon)


@pytest.mark.parametrize("knob,value", [("kv_paged", True),
                                        ("refill_horizon", 48)])
def test_engine_config_rejects_removed_knobs(knob, value):
    """The layout knobs are gone: refill=True alone selects paged slots."""
    import dataclasses
    names = {f.name for f in dataclasses.fields(EngineConfig)}
    assert knob not in names
    assert {"refill", "kv_page_size", "kv_pool_pages", "kv_kernel"} <= names
    with pytest.raises(TypeError, match=knob):
        EngineConfig(estimator=None, retriever=None, library=None,
                     **{knob: value})


def test_stream_refill_reports_pages(real_engine):
    """A predict_stream(refill=True) run pages its KV: SchedulerStats
    carries the pool's page size, peak pages and peak live tokens, and
    every page is back in the pool when the stream ends."""
    mk, data = real_engine
    queries = [data.queries[int(q)] for q in data.test_qids[:5]]
    sched = MicrobatchScheduler(BucketConfig(batch_sizes=(1, 2, 4, 8)))
    pools = list(mk(refill=True).predict_stream(
        (RouteRequest(t) for t in (queries[:2], queries[2:])),
        scheduler=sched, segment_len=3))
    assert len(pools) == 2
    st = sched.stats
    assert st.kv_page_size == 16 and st.pages_peak > 0
    assert st.kv_peak_tokens > 0 and st.pages_in_use == 0
    assert 0.0 <= st.page_fragmentation < 1.0
    kv = st.as_dict()["kv_pages"]
    assert kv["peak"] == st.pages_peak and "deferred_on_horizon" not in kv
