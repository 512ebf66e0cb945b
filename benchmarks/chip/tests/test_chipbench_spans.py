"""The program's spans and named scopes read from a trace
(``harness/program_trace.py``) and the per-layer metrics that read them,
on written traces (no chip needed)."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from harness import program_trace as pt  # noqa: E402
from harness import spec, trace_reduce  # noqa: E402

MS = 1e6    # ns
DECODE = "jit__paged_scan_decode"
REFILL = "jit__paged_refill_scan_decode"
BASE_KEYS = ("window_s", "busy_s", "modules", "device_ops")


def ev(name, start_ms, dur_ms, module=""):
    return [name, start_ms * MS, dur_ms * MS, module]


def step(scope):
    return f"jit(_paged_scan_decode)/while/body/closed_call/decode/{scope}"


SCOPES = {
    DECODE: {"%fusion.1": step("while/body/closed_call/attn/dot_general"),
             "%fusion.2": step("while/body/closed_call/mlp/dot_general"),
             "%fusion.5": step("sample/argmax")},
    # the same short name means another op in another executable
    REFILL: {"%fusion.7": "jit(_paged_refill_scan_decode)/prefill/while/"
                          "body/closed_call/attn/dot_general",
             "%fusion.1": "jit(_paged_refill_scan_decode)/prefill/while/"
                          "body/closed_call/mlp/mul",
             "%fusion.8": step("while/body/closed_call/attn/dot_general")},
}


def fixture(program=True):
    """A window of 100 ms: four plain decode segments and one refill, idle
    gaps at 10-20 (under a boundary), 50-60 (under a decision), 70-80
    (under nothing) and 85-95 (under the harness's wait)."""
    mods = [ev(f"{DECODE}(7)", 0, 10), ev(f"{REFILL}(9)", 20, 30),
            ev(f"{DECODE}(7)", 60, 10), ev(f"{DECODE}(7)", 80, 5),
            ev(f"{DECODE}(7)", 95, 5)]
    ops = [ev("%fusion.1 = bf16[64] fusion(...)", 0, 6),
           ev("%fusion.2", 6, 3), ev("%copy.3", 9, 1),
           ev("%fusion.7", 20, 14), ev("%fusion.1", 34, 4),
           ev("%while.9 = (s32[]) while(...)", 38, 12),
           ev("%fusion.8", 38, 10),
           ev("%fusion.1", 60, 6), ev("%fusion.2", 66, 3),
           ev("%copy.3", 69, 1),
           ev("%fusion.1", 80, 4), ev("%fusion.5", 84, 1),
           ev("%fusion.1", 95, 5)]
    host = [ev("bench.traced", 0, 100), ev("bench.wait", 86, 8),
            ev("bench.decide", 52, 5), ev("unrelated", 10, 5)]
    if program:
        host += [ev("scope.boundary", -8, 5),          # before the window
                 ev("scope.pump", 10, 10), ev("scope.boundary", 12, 7),
                 ev("scope.launch", 18, 2), ev("scope.prepare", 30, 4),
                 ev("scope.decide", 53, 3), ev("scope.prepare", 57, 5),
                 ev("scope.boundary", 86, 2)]
    trace = {"planes": [
        {"name": "/host:CPU",
         "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops},
                   {"name": "XLA Modules", "events": mods}]}]}
    if program:
        trace["scopes"] = SCOPES
    return trace


def test_the_existing_keys_are_unchanged_by_spans_and_scopes():
    plain = trace_reduce.reduce(fixture(program=False))
    for red in (pt.reduce(fixture()), trace_reduce.reduce(fixture()),
                pt.reduce(fixture(program=False))):
        assert {k: red[k] for k in BASE_KEYS} == \
            {k: plain[k] for k in BASE_KEYS}
        assert sorted(g[1] for g in red["idle_gaps"]) == \
            sorted(g[1] for g in plain["idle_gaps"])
    assert plain["busy_s"] == pytest.approx(0.060)


def test_gaps_are_named_by_the_innermost_span_else_engine():
    got = sorted((round(s * 1e3, 6), n)
                 for n, s in pt.reduce(fixture())["idle_gaps"])
    assert got == [(10.0, "bench.wait"), (10.0, "engine"),
                   (10.0, "scope.boundary"), (10.0, "scope.decide")]
    plain = sorted(n for n, _ in
                   trace_reduce.reduce(fixture(program=False))["idle_gaps"])
    assert plain == ["bench.decide", "bench.wait", "engine", "engine"]


def test_spans_idle_under_spans_and_scope_seconds():
    red = pt.reduce(fixture())
    spans = {k: [round(s * 1e3, 6) for s in v]
             for k, v in red["spans"].items()}
    assert spans == {"scope.pump": [10.0], "scope.boundary": [7.0, 2.0],
                     "scope.launch": [2.0], "scope.prepare": [4.0, 5.0],
                     "scope.decide": [3.0]}
    # idle [10,20) + [53,56) + [57,60) + [86,88) lies under scope.* spans
    assert red["host_bound_idle_s"] == pytest.approx(0.018)
    ms = {m: {k: round(v * 1e3, 6) for k, v in per.items()}
          for m, per in red["scope_s"].items()}
    assert ms == {DECODE: {"decode/attn": 21.0, "decode/mlp": 6.0,
                           "decode/sample": 1.0, "other": 2.0},
                  REFILL: {"prefill/attn": 14.0, "prefill/mlp": 4.0,
                           "decode/attn": 10.0, "other": 2.0}}
    # without a table every op is ``other``, module by module
    bare = pt.reduce(fixture(program=False))["scope_s"]
    assert set(bare) == {DECODE, REFILL}
    assert all(set(per) == {"other"} for per in bare.values())


def read(name, red):
    run = SimpleNamespace(trace=red, cell=SimpleNamespace(
        config={"engine": {"segment_len": 4}}))
    return spec.metric_reader(name)(run)


EXPECTED = {
    "host_bound_idle_share": 18.0,          # 18 ms of 100
    "host_bound_idle_share.backlog": 18.0,
    "boundary_host_ms_p95": 2.0 + 0.95 * 5.0,   # of [7, 2] ms
    "boundary_host_ms_p95.backlog": 2.0 + 0.95 * 5.0,
    "decode_attn_ms": 21.0 / (4 * 4),       # 4 launches of 4 steps
    "decode_attn_ms.backlog": 21.0 / (4 * 4),
    "refill_prefill_ms": 18.0,              # one launch
    "prepare_ms_p95": 4.0 + 0.95 * 1.0,     # of [4, 5] ms
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_by_hand(name):
    assert read(name, pt.reduce(fixture())) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_reads_nothing_without_program_spans(name):
    assert read(name, pt.reduce(fixture(program=False))) is None
    assert read(name, trace_reduce.reduce(fixture())) is None
    assert read(name, None) is None


def test_the_program_metrics_are_laid_out_as_the_benchmarks():
    bench = spec.load_json(BENCH.parents[1] / "BENCHMARK.json")
    extra = spec.load_json(BENCH / "program_metrics.json")["per_layer"]
    assert sorted(m["name"] for m in extra) == sorted(EXPECTED)
    cells = {w["name"] for w in bench["workloads"]}
    have = {m["name"] for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    for m in extra:
        assert set(m) == set(bench["per_layer"][0])
        assert m["name"] not in have and m["layer"] in layers
        assert set(m["workloads"]) <= cells
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        backlog = m["name"].endswith(".backlog")
        assert m["moves"] == ("decisions_per_s" if backlog
                              else "ttd_p95_ms")


# -- the op_name paths of a TPU trace, from the wire format -----------------
def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _num(field, n):
    return _varint(field << 3) + _varint(n)


def _msg(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, ops, stat_ids):
    """An XPlane: stat metadata, one event-metadata entry per op (program
    id as int64, tf_op as a string or a reference), and a line."""
    body = _msg(2, name)
    for sname, sid in stat_ids.items():
        body += _msg(5, _num(1, sid) + _msg(2, _num(1, sid) + _msg(2, sname)))
    for i, (op, program, path, by_ref) in enumerate(ops, 1):
        tf_op = (_num(1, stat_ids["tf_op"])
                 + (_num(7, stat_ids[path]) if by_ref else _msg(5, path)))
        meta = (_num(1, i) + _msg(2, op)
                + _msg(5, _num(1, stat_ids["program_id"]) + _num(4, program))
                + _msg(5, tf_op)
                + _msg(5, _num(1, stat_ids["flops"]) + b"\x11" + b"\0" * 8))
        body += _msg(4, _num(1, i) + _msg(2, meta))
    body += _msg(3, _num(1, 1) + _msg(2, "XLA Ops") + _msg(4, _num(1, 1)))
    return _msg(1, body)


def test_op_paths_are_read_from_the_event_metadata():
    ids = {"program_id": 3, "tf_op": 9, "flops": 4,
           "jit(f)/decode/attn/dot:": 12}
    data = (_plane("/host:CPU", [("%x", 11, "jit(f)/attn/x:", False)], ids)
            + _plane("/device:TPU:0", [
                ("%fusion.1 = bf16[8] fusion(...)", 11,
                 "jit(f)/while/body/closed_call/decode/mlp/dot_general:",
                 False),
                ("%fusion.2", 11, "jit(f)/decode/attn/dot:", True),
                ("%fusion.1", 2 ** 62 + 5, "jit(g)/prefill/attn/dot:",
                 False)], ids))
    got = pt.op_paths(data)
    assert got == [
        (11, "%fusion.1", "jit(f)/while/body/closed_call/decode/mlp/"
                          "dot_general"),
        (11, "%fusion.2", "jit(f)/decode/attn/dot"),
        (2 ** 62 + 5, "%fusion.1", "jit(g)/prefill/attn/dot")]
    trace = fixture(program=False)
    trace["planes"][1]["lines"][1]["events"] += [
        ev("jit_f(11)", 200, 1), ev(f"jit_g({2 ** 62 + 5})", 210, 1)]
    table = pt.scope_table(trace, got)
    assert table == {"jit_f": {"%fusion.1": got[0][2],
                               "%fusion.2": got[1][2]},
                     "jit_g": {"%fusion.1": got[2][2]}}
    assert [pt.scope_key(p) for _, _, p in got] == [
        "decode/mlp", "decode/attn", "prefill/attn"]
    assert pt.scope_key("jit(f)/while/body/add") == "other"


def test_flatten_adds_program_spans_and_keeps_the_rest(tmp_path):
    """On a real (CPU) trace: the harness's flattening, plus the
    ``scope.*`` events, four fields each, and an empty table (a CPU trace
    carries no ``tf_op``)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("attn"):
            return jnp.tanh(x @ x)

    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.traced"):
            with jax.profiler.TraceAnnotation("scope.pump"):
                with jax.profiler.TraceAnnotation("scope.launch"):
                    f(x).block_until_ready()
    pb = str(sorted(tmp_path.glob("**/*.xplane.pb"))[-1])
    base, ours = trace_reduce.flatten(pb), pt.flatten(pb)
    assert ours.pop("scopes") == {}
    added = []
    for p, q in zip(base["planes"], ours["planes"], strict=True):
        assert p["name"] == q["name"]
        for ln in q["lines"]:
            was = next((b["events"] for b in p["lines"]
                        if b["name"] == ln["name"]), [])
            assert ln["events"][: len(was)] == was
            added += ln["events"][len(was):]
    assert sorted(e[0] for e in added) == ["scope.launch", "scope.pump"]
    assert all(len(e) == 4 for e in added)
    spans = pt.span_seconds(trace_reduce.host_spans(ours, pt.SPAN_PREFIX),
                            *trace_reduce.window(ours))
    assert set(spans) == {"scope.pump", "scope.launch"}
    assert spans["scope.launch"][0] <= spans["scope.pump"][0]
