"""The harness end to end at a CPU size, past its look for a chip: a sound
run is correct, and the lower-precision control and faults planted in the
timed path are not."""
import dataclasses
import io
import sys
import time
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

import run_cell  # noqa: E402
from harness import runner, spec  # noqa: E402

SECONDS = "3"


@pytest.fixture(scope="module")
def session():
    """One tiny-width qwen3-4b.poisson session (the tests' configuration
    file), kept across the tests so that its executables compile once."""
    cell = spec.load_cell("qwen3-4b.poisson")
    cell.config = spec.load_json(BENCH / "tests" / "data" / "tiny.json")
    cell.params = {"rate_qps": 3.0}
    return runner.Session(cell, time.perf_counter(), out_dir=BENCH / "out")


def run(session, seed, control="none"):
    args = run_cell.parse_args(["--workload", session.cell.name, "--seed",
                                str(seed), "--seconds", SECONDS,
                                "--control", control])
    import jax
    device = run_cell.device_info(jax)
    with redirect_stderr(io.StringIO()):
        got, rc = run_cell.one_seed(session, seed, args, device, None)
    assert rc == 0
    return got


def test_a_sound_run_is_correct(session):
    run_, metrics, check, _, _ = run(session, 5_000_000_123)
    assert check["correct"], check["checks"]
    assert run_.attempted > 0 and run_.failed == 0
    assert set(metrics) == {"ttd_p50_ms", "ttd_p95_ms", "decisions_per_s",
                            "setup_s"}
    assert check["pairs"] == check["queries"] * 11


def test_a_closed_loop_counts_every_query_it_sent(session):
    """Once the window's time is up the loop sends nothing more and waits
    for what it sent: the rate spans every query sent, over the time until
    the last was decided, so it does not step with the close's phase."""
    cell = spec.load_cell("qwen3-4b.backlog")
    cell.config = session.cell.config
    closed = runner.Session(cell, time.perf_counter(), out_dir=BENCH / "out")
    run_, metrics, check, _, _ = run(closed, 19)
    assert check["correct"], check["checks"]
    assert run_.decided == run_.attempted > 0 and run_.failed == 0
    assert run_.rate_s >= float(SECONDS)
    assert set(metrics) == {"decisions_per_s", "setup_s"}
    assert metrics["decisions_per_s"]["value"] == run_.decided / run_.rate_s


def test_the_lower_precision_control_is_not_correct(session):
    _, _, check, _, _ = run(session, 7, control="fp8")
    ctl = check["control"]["checks"]["logit_gap"]
    assert check["correct"]
    assert not check["control"]["correct"]
    assert ctl["value"] > 3 * check["checks"]["logit_gap"]["value"]


def test_a_token_altered_where_it_is_produced_is_caught(session,
                                                        monkeypatch):
    from repro.serving import sampler
    real = sampler.decode_segment

    def altered(*a, **kw):
        state, gen, dec = real(*a, **kw)
        return state, gen.at[:, -1].add(1), dec

    monkeypatch.setattr(sampler, "decode_segment", altered)
    _, _, check, _, _ = run(session, 11)
    assert not check["correct"]
    assert check["checks"]["logit_gap"]["value"] > \
        check["checks"]["logit_gap"]["limit"]


def test_an_answer_altered_where_it_is_produced_is_caught(session,
                                                         monkeypatch):
    from repro.core import estimator
    real = estimator.parse_generations

    def altered(*a, **kw):
        batch = real(*a, **kw)
        return dataclasses.replace(batch, y_hat=1 - np.asarray(batch.y_hat))

    monkeypatch.setattr(estimator, "parse_generations", altered)
    _, _, check, _, _ = run(session, 13)
    assert not check["correct"]
    assert check["checks"]["parse_mismatch"]["value"] > 0


def test_a_routed_decision_altered_is_caught(session, monkeypatch):
    from repro.api.policy import FixedAlphaPolicy
    real = FixedAlphaPolicy.decide

    def altered(self, pool, engine):
        d = real(self, pool, engine)
        return dataclasses.replace(
            d, choices=(np.asarray(d.choices) + 1) % len(pool.models))

    monkeypatch.setattr(FixedAlphaPolicy, "decide", altered)
    _, _, check, _, _ = run(session, 17)
    assert not check["correct"]
    assert check["checks"]["decision_gap"]["value"] > \
        check["checks"]["decision_gap"]["limit"]


def test_without_a_tpu_it_exits_1_and_prints_no_result():
    out = io.StringIO()
    with redirect_stdout(out), redirect_stderr(io.StringIO()):
        rc = run_cell.main(["--workload", "qwen3-4b.poisson", "--seed", "1",
                            "--seconds", "1", "--trace", "0"])
    assert rc == 1 and out.getvalue() == ""
