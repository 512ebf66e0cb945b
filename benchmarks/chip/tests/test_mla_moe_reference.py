"""The DeepSeek-V2 family's plain reference (``references/mla_moe.py``)
and operation counts (``harness/flops_mla_moe.py``), and the harness end
to end on the family at a CPU size."""
import io
import sys
import time
from contextlib import redirect_stderr
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

import run_cell  # noqa: E402
from harness import flops_mla_moe as F  # noqa: E402
from harness import placement, runner, spec  # noqa: E402

CONFIG = spec.load_json(BENCH / "configs" / "deepseek-v2-lite.json")
TINY = spec.load_json(BENCH / "tests" / "data" / "tiny-mla-moe.json")
REF = spec.reference_module(CONFIG)


def _layout(tree):
    return jax.tree.map(lambda a: (a.shape, a.dtype), tree)


@pytest.mark.parametrize("config", [CONFIG, TINY], ids=["published", "tiny"])
def test_make_params_has_the_programs_layout(config):
    """``make_params`` is ``init_params`` and a relabelling of the router's
    columns (``test_placement_relabels_the_routers_columns_only``)."""
    from repro.models import model as M
    cfg = runner.program_config(config)
    want = jax.eval_shape(lambda k: M.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: REF.init_params(jax.random.PRNGKey(7),
                                                 config["model"]))
    assert _layout(got) == _layout(want)


def test_placement_relabels_the_routers_columns_only():
    """At a chip's share, ``make_params`` permutes each expert layer's
    router columns so that the held experts carry the chip's fair share of
    the routed pairs of the sample prompts; every other weight is
    ``init_params``'."""
    m = TINY["model"]
    placed = REF.make_params(3, m)
    plain = jax.jit(lambda k: REF.init_params(k, m))(jax.random.PRNGKey(3))
    assert _layout(placed) == _layout(plain)
    router = ("segments", 1, "0", "moe", "router")
    flat = dict(jax.tree_util.tree_flatten_with_path(plain)[0])
    for path, leaf in jax.tree_util.tree_flatten_with_path(placed)[0]:
        keys = tuple(getattr(k, "key", getattr(k, "idx", None))
                     for k in path)
        if keys != router:
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(flat[path]))
            continue
        got, was = np.asarray(leaf), np.asarray(flat[path])
        for layer in range(got.shape[0]):
            cols = {c.tobytes(): i for i, c in enumerate(was[layer].T)}
            perm = [cols[c.tobytes()] for c in got[layer].T]
            assert sorted(perm) == list(range(m["num_experts"]))


def test_held_first_takes_the_fair_share():
    """The held set's total is the nearest to held / num_experts of the
    pairs that one exchange at a time reaches, from the experts nearest
    the mean; the permutation puts it at the chip's ids."""
    counts = np.array([100, 0, 5, 40, 30, 20, 10, 3])
    order = placement.held_first(counts, 4)
    assert sorted(order) == list(range(8))
    assert counts[order[:4]].sum() == 100          # fair share 104
    assert list(placement.held_first(counts, 4, offset=4)[4:]) == \
        sorted(order[:4])
    # a hot expert stays off the chip when the rest can make its share
    assert 0 not in order[:4]


def test_operation_counts_match_the_published_sizes():
    m = CONFIG["model"]
    assert F.attention_params(m) == 13_762_560
    assert F.dense_mlp_params(m) == 67_239_936
    assert F.expert_params(m) == 8_650_752
    assert F.shared_params(m) == 17_301_504
    assert F.router_params(m) == 131_072
    assert F.held_params(m) == 3_110_862_848
    # whole, with 64 experts held, the model is 15.71B parameters
    assert F.held_params(dict(m, experts_held=64)) == 15_706_357_760
    assert round(F.step_weight_bytes(m) / 1e9, 2) == 5.80
    # the held routed experts are 62% of a decode step's weight bytes;
    # with the shared experts and the router, 78%
    held = F.expert_layers(m) * F.held_experts(m) * F.expert_params(m) * 2
    assert round(held / F.step_weight_bytes(m), 2) == 0.62
    assert round(F.expert_layer(m, 512)["bytes"]
                 / F.step_weight_bytes(m), 2) == 0.78
    assert F.latent_bytes_per_token(m) == 31_104
    assert round(512 * 80 * F.latent_bytes_per_token(m) / 1e9, 2) == 1.27
    # a 512-row decode step is weight-bound at ~7.1 ms of weight reads
    step = F.decode_step(m, 512, 55.0)
    assert step["bytes"] / 819e9 > step["flops"] / 197e12
    assert F.step_weight_bytes(m) / 819e9 == pytest.approx(7.08e-3, rel=1e-2)


def _small_params():
    m = dict(TINY["model"], dtype="float32")
    return m, REF.make_params(3, m)


def _readout_inputs(m, rows=3, width=20, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, m["vocab_size"], size=(rows, width))
    r = np.repeat(np.arange(rows), width)
    c = np.tile(np.arange(width), rows)
    t = rng.integers(0, m["vocab_size"], size=r.shape)
    return tokens.astype(np.int32), r, c, t


def test_readout_agrees_with_the_programs_reference():
    """The benchmark's reference and ``repro.models.reference``, written
    apart, give the same logits on the same weights."""
    from repro.models import reference as R
    m, params = _small_params()
    cfg = runner.program_config(dict(TINY, model=m,
                                     overrides=dict(TINY["overrides"],
                                                    dtype="float32")))
    tokens, r, c, t = _readout_inputs(m)
    got = REF.readout(params, m, tokens, r, c, t, (1, 2), block=16)
    logits = np.asarray(R.head(params, cfg, R.hidden(params, cfg, tokens)))
    want = logits[r, c]
    np.testing.assert_allclose(got["max"], want.max(-1), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got["at_target"], want[np.arange(len(t)), t],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got["extra"], want[:, [1, 2]], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(got["argmax"], want.argmax(-1))


def test_the_fp8_control_differs_from_the_reference():
    m, params = _small_params()
    tokens, r, c, t = _readout_inputs(m)
    ref = REF.readout(params, m, tokens, r, c, t, block=16)
    ctl = REF.readout(params, m, tokens, r, c, t, block=16, control="fp8")
    gap = np.abs(ctl["at_target"] - ref["at_target"])
    assert gap.max() > 0.01
    # the control's best token is not the reference's best everywhere
    assert (ctl["argmax"] != ref["argmax"]).any()


@pytest.fixture(scope="module")
def session():
    """One tiny-width deepseek-v2-lite.backlog-wide session."""
    cell = spec.load_cell("deepseek-v2-lite.backlog-wide")
    cell.config = TINY
    cell.traffic = dict(cell.traffic, arrival={"process": "closed",
                                               "outstanding": 2})
    return runner.Session(cell, time.perf_counter(), out_dir=BENCH / "out")


def _run(session, seed, control="none"):
    args = run_cell.parse_args(["--workload", session.cell.name, "--seed",
                                str(seed), "--seconds", "3", "--control",
                                control])
    with redirect_stderr(io.StringIO()):
        got, rc = run_cell.one_seed(session, seed, args,
                                    run_cell.device_info(jax), None)
    assert rc == 0
    return got


def test_a_sound_run_is_correct_and_the_control_is_not(session):
    run_, metrics, check, _, _ = _run(session, 5_000_000_321,
                                      control="fp8")
    assert check["correct"], check["checks"]
    assert run_.attempted > 0 and run_.failed == 0
    assert set(metrics) == {"decisions_per_s", "setup_s"}
    assert not check["control"]["correct"]
    assert check["control"]["checks"]["logit_gap"]["value"] > \
        3 * check["checks"]["logit_gap"]["value"]


def test_the_cells_metrics_read_a_traced_run():
    """The new per-layer readers, on a run record carrying a reduced
    trace: shares lie in (0, 100]."""
    cell = spec.load_cell("deepseek-v2-lite.backlog-wide")
    assert {m["name"] for m in cell.per_layer} == {
        "decode_step_roofline.backlog-wide", "estimator_mfu.backlog-wide",
        "device_idle_share.backlog-wide"}
    run_ = runner.Run(
        cell=cell, seed=1, seconds=50.0, setup_s=1.0, ttd_ms=np.zeros(1),
        intake_ms=np.zeros(1), hit_wait_ms=np.zeros(0), decided=1,
        rate_s=50.0, attempted=1, failed=0,
        counters={"emitted": 1000, "real_tokens": 49_000,
                  "slot_steps_active": 12_000, "slot_steps_total": 20_000},
        trace={"window_s": 50.0, "busy_s": 45.0,
               "modules": {"jit__paged_scan_decode": {"count": 100,
                                                      "seconds": 8.0}}},
        device_kind="TPU v5 lite")
    got = {m["name"]: spec.metric_reader(m["name"])(run_)
           for m in cell.per_layer}
    assert got["device_idle_share.backlog-wide"] == pytest.approx(10.0)
    for name in ("decode_step_roofline.backlog-wide",
                 "estimator_mfu.backlog-wide"):
        assert 0.0 < got[name] <= 100.0, (name, got[name])
    # least time of a 4-step segment: 4 x (5.80 GB + latents) / 819 GB/s
    m = cell.config["model"]
    least = 4 * F.decode_step(m, 512, 49 + 6)["bytes"] / 819e9
    assert got["decode_step_roofline.backlog-wide"] == pytest.approx(
        100 * least / 0.08)
    # refill launches add the prefill of their share of the admitted
    # prompts (1,000 of 49 real tokens over 50 launches)
    run_.trace["modules"]["jit__paged_refill_scan_decode"] = {
        "count": 50, "seconds": 30.0}
    per = F.prefill(m, 1000 / 50, 49.0)
    prefill_s = max(per["flops"] / 197e12, per["bytes"] / 819e9)
    got = spec.metric_reader("decode_step_roofline.backlog-wide")(run_)
    assert got == pytest.approx(100 * (150 * least + 50 * prefill_s) / 38.0)
    assert 0.0 < got <= 100.0
    run_.trace = None
    assert spec.metric_reader("estimator_mfu.backlog-wide")(run_) is None
