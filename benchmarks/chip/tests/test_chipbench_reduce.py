"""Trace reduction, operation and byte counts, and the peaks table, on a
written trace (no chip needed)."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import flops, spec, trace_reduce  # noqa: E402

MS = 1e6    # ns


def ev(name, start_ms, dur_ms, module=""):
    return [name, start_ms * MS, dur_ms * MS, module]


def fixture(with_modules=True):
    """A window of 100 ms: two executables, one idle gap under a harness
    span, ops that overlap each other and the window's edge."""
    ops = [ev("fusion.1", -5, 10, "jit__paged_scan_decode"),       # 0-5 in
           ev("fusion.2", 3, 7, "jit__paged_scan_decode"),         # overlaps
           ev("convolution.3", 20, 30, "jit__paged_refill_scan_decode"),
           ev("fusion.1", 60, 10, "jit__paged_scan_decode"),
           ev("fusion.4", 95, 10, "jit__paged_scan_decode")]       # 95-100
    mods = [ev("jit__paged_scan_decode(7)", -5, 15),
            ev("jit__paged_refill_scan_decode(9)", 20, 30),
            ev("jit__paged_scan_decode(7)", 60, 10),
            ev("jit__paged_scan_decode(7)", 95, 10)]
    dev_lines = [{"name": "XLA Ops", "events": ops}]
    if with_modules:
        dev_lines.append({"name": "XLA Modules", "events": mods})
    host = [ev("bench.traced", 0, 100), ev("bench.wait", 72, 20),
            ev("bench.decide", 52, 5), ev("unrelated", 10, 5)]
    return {"planes": [{"name": "/host:CPU",
                        "lines": [{"name": "python", "events": host}]},
                       {"name": "/device:TPU:0", "lines": dev_lines}]}


@pytest.mark.parametrize("with_modules", [True, False])
def test_busy_is_a_union_of_intervals_and_idle_share_follows(with_modules):
    """Executables' runs where the module line exists, else the ops:
    [0,10) + [20,50) + [60,70) + [95,100) = 55 ms either way here."""
    red = trace_reduce.reduce(fixture(with_modules))
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.055)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.45)


def test_busy_counts_a_whole_executable_run():
    """Ops that leave gaps inside a run do not make the device idle."""
    tr = fixture()
    ops = tr["planes"][1]["lines"][0]["events"]
    ops[:] = [e for e in ops if e[0] != "convolution.3"]
    ops.append(ev("fusion.7", 20, 5, "jit__paged_refill_scan_decode"))
    assert trace_reduce.reduce(tr)["busy_s"] == pytest.approx(0.055)


def test_a_device_plane_without_ops_is_not_a_chip():
    tr = fixture()
    tr["planes"].append({"name": "/device:CUSTOM:Megascale Trace",
                         "lines": []})
    assert trace_reduce.reduce(tr)["busy_s"] == pytest.approx(0.055)


def test_busy_averages_device_planes():
    tr = fixture()
    second = json.loads(json.dumps(tr["planes"][1]))
    second["name"] = "/device:TPU:1"
    second["lines"] = [{"name": "XLA Ops",
                        "events": [ev("fusion.9", 0, 100)]}]
    tr["planes"].append(second)
    busy, _ = trace_reduce.busy(tr, 0, 100 * MS)
    assert busy == pytest.approx((0.055 + 0.100) / 2)


@pytest.mark.parametrize("with_modules,decode_s", [(True, 0.020),
                                                   (False, 0.027)])
def test_per_executable_device_time(with_modules, decode_s):
    """From the module line: launches that start in the window (60 and 95
    ms).  Without it: the ops that start in the window, grouped by their
    module, one launch per run of one module's ops."""
    mods = trace_reduce.reduce(fixture(with_modules))["modules"]
    dec = mods["jit__paged_scan_decode"]
    ref = mods["jit__paged_refill_scan_decode"]
    assert ref["count"] == 1 and ref["seconds"] == pytest.approx(0.030)
    assert dec["count"] == 2
    assert dec["seconds"] == pytest.approx(decode_s)


def test_idle_gaps_are_named_by_the_host_span_over_them():
    gaps = dict((n, s) for n, s in
                trace_reduce.reduce(fixture())["idle_gaps"])
    assert gaps["bench.wait"] == pytest.approx(0.025)      # 70-95
    assert gaps["bench.decide"] == pytest.approx(0.010)    # 50-60
    assert gaps["engine"] == pytest.approx(0.010)          # 10-20


def test_top_ops_by_device_time():
    top = trace_reduce.reduce(fixture())["device_ops"]
    assert top[0][0] == "convolution.3"
    assert top[0][1] == pytest.approx(0.030)


def test_a_trace_without_its_window_span_is_refused():
    tr = fixture()
    tr["planes"][0]["lines"][0]["events"] = []
    with pytest.raises(ValueError):
        trace_reduce.reduce(tr)


def qwen3():
    return spec.load_json(BENCH / "configs" / "qwen3-4b.json")["model"]


def test_parameter_count_matches_the_published_size():
    assert flops.params(qwen3()) == 4_022_468_096
    intern = spec.load_json(BENCH / "configs" / "internlm2-1.8b.json")
    assert flops.params(intern["model"]) == 1_889_110_016


def test_roofline_bound_chosen():
    peak = spec.peaks("TPU v5 lite")
    m = qwen3()
    # a 64-slot decode step reads 8 GB and computes 0.5 TFLOP: memory
    step = flops.least_time(flops.decode_step(m, 64, 55), peak)
    assert step["bound"] == "memory"
    assert step["seconds"] == pytest.approx(
        (flops.matmul_params(m) * 2 + 64 * 55 * flops.kv_bytes_per_token(m))
        / 819e9)
    # a 64 x 64 prefill computes 3.3e13: compute
    pre = flops.least_time(flops.prefill(m, 64, 64), peak)
    assert pre["bound"] == "compute"
    assert pre["seconds"] > 0.16


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


def test_top_ops_count_a_loop_by_its_self_time():
    tr = fixture()
    ops = tr["planes"][1]["lines"][0]["events"]
    ops.append(ev("%while.9 = (s32[], bf16[64]) while(...)", 18, 34))
    top = dict(trace_reduce.reduce(tr)["device_ops"])
    # 34 ms of loop around the 30 ms convolution inside it
    assert top["%while.9"] == pytest.approx(0.004)
    assert top["convolution.3"] == pytest.approx(0.030)
