"""The traffic generator and the discovery of files by name."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import spec, traffic  # noqa: E402

BIG = 5_000_000_017          # seeds reach past 32 signed bits


def mix(name):
    return spec.load_json(BENCH / "traffic" / f"{name}.json")


def test_every_seed_gets_the_same_arrivals():
    a = traffic.schedule(mix("poisson"), {"rate_qps": 6.0}, 30.0, BIG)
    c = traffic.schedule(mix("poisson"), {"rate_qps": 6.0}, 30.0, BIG + 1)
    assert np.array_equal(a.arrivals, c.arrivals)
    assert a.n_counted == 180
    assert a.arrivals[a.n_counted - 1] < 30.0 <= a.arrivals[a.n_counted]
    assert (np.diff(a.arrivals) > 0).all()
    # the mix's schedule seed orders the same gaps another way
    other = dict(mix("poisson"), schedule_seed=13)
    b = traffic.schedule(other, {"rate_qps": 6.0}, 30.0, BIG)
    assert not np.array_equal(a.arrivals, b.arrivals)
    ga = np.diff(np.concatenate([[0.0], a.arrivals[: a.n_counted]]))
    gb = np.diff(np.concatenate([[0.0], b.arrivals[: b.n_counted]]))
    assert np.allclose(np.sort(ga), np.sort(gb))


def test_gaps_follow_the_exponential_law():
    g = traffic.exponential_gaps(4000, 1000.0)
    assert g.sum() == pytest.approx(1000.0)
    assert np.median(g) / g.mean() == pytest.approx(np.log(2), rel=0.01)


def test_hot_repeat_has_its_share_and_set_size():
    m = mix("hot-repeat")
    s = traffic.schedule(m, {"rate_qps": 6.0}, 40.0, BIG)
    slots = s.slots[: s.n_counted]
    assert s.n_hot == round(0.9 * s.n_counted)
    assert slots[slots >= 0].max() < 200 and slots.min() == -1
    assert traffic.hot_set_size(m) == 200
    # Zipf(1.0): the first hot query is drawn most
    counts = np.bincount(slots[slots >= 0], minlength=200)
    assert counts[0] == counts.max()
    # the same arrivals are hot for every seed; the seed draws which hot
    # query each one repeats
    again = traffic.schedule(m, {"rate_qps": 6.0}, 40.0, BIG)
    other = traffic.schedule(m, {"rate_qps": 6.0}, 40.0, BIG + 1)
    assert np.array_equal(again.slots, s.slots)
    assert np.array_equal(other.slots >= 0, s.slots >= 0)
    assert not np.array_equal(other.slots, s.slots)


def test_query_draws_repeat_with_the_seed():
    r1 = traffic.rng_for(BIG, 1).integers(0, 1 << 30, 8)
    r2 = traffic.rng_for(BIG, 1).integers(0, 1 << 30, 8)
    r3 = traffic.rng_for(BIG + 1, 1).integers(0, 1 << 30, 8)
    assert np.array_equal(r1, r2) and not np.array_equal(r1, r3)
    assert 0 <= traffic.seed32(BIG, 4) < 2 ** 31
    assert traffic.seed32(BIG, 4) != traffic.seed32(BIG + 1, 4)


def test_closed_loop_has_no_arrivals():
    s = traffic.schedule(mix("backlog"), {}, 30.0, BIG)
    assert s.outstanding == 12 and len(s.arrivals) == 0


def test_due_takes_at_most_the_cap():
    arr = np.array([0.1, 0.2, 0.3, 0.9])
    assert traffic.due(arr, 0, 0.35, 2) == [0, 1]
    assert traffic.due(arr, 2, 0.35, 12) == [2]
    assert traffic.due(arr, 3, 0.35, 12) == []


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of the benchmark's data files, as a later change sees them."""
    root = tmp_path / "repo"
    dst = root / "benchmarks" / "chip"
    for d in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(BENCH / d, dst / d)
    shutil.copy(BENCH / "peaks.json", dst / "peaks.json")
    shutil.copy(BENCH.parents[1] / "BENCHMARK.json", root / "BENCHMARK.json")
    return root, dst


def test_files_dropped_in_are_found_by_name(bench_copy):
    root, dst = bench_copy
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((dst / "configs" / "qwen3-4b.json").read_text())
    cfg["name"] = "new-model"
    (dst / "configs" / "new-model.json").write_text(json.dumps(cfg))
    m = mix("poisson")
    m["name"], m["arrival"] = "bursty", {"process": "poisson"}
    (dst / "traffic" / "bursty.json").write_text(json.dumps(m))
    (dst / "cells" / "new-model.bursty.json").write_text(
        json.dumps({"rate_qps": 2.5}))
    (dst / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["workloads"].append({"name": "new-model.bursty",
                               "config": "new-model", "traffic": "bursty",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "decisions_per_s",
                               "workloads": ["new-model.bursty"]})
    cell = spec.load_cell("new-model.bursty", bench, bench_dir=dst)
    assert cell.config["name"] == "new-model"
    assert cell.traffic["name"] == "bursty"
    assert cell.params == {"rate_qps": 2.5}
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    # a metric without a cell list follows its end-to-end metric
    assert {m["name"] for m in cell.end_to_end} == {"decisions_per_s",
                                                    "setup_s"}
    assert spec.metric_reader("new_metric", bench_dir=dst)(None) == 42.0


def test_every_listed_metric_has_a_reader_and_every_cell_its_files():
    bench = spec.load_json(BENCH.parents[1] / "BENCHMARK.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.end_to_end and cell.per_layer
        if cell.traffic["arrival"]["process"] == "poisson":
            assert cell.params["rate_qps"] > 0


def test_an_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell")
