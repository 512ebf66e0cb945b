"""Host spans and named scopes on the serve path, and the slot runtime's
program counters.

The serve path writes ``scope.*`` profiler spans (``TraceAnnotation``) on
the host and names the estimator's layers with ``jax.named_scope``; both
land in the profiler's one timeline.  The slot runtime adds its slot-step
and prefill-row counters to ``SchedulerStats`` at every segment boundary.
"""
import contextlib
import functools
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EngineConfig, RouteRequest, ScopeEngine
from repro.api.policy import FixedAlphaPolicy
from repro.configs.scope_estimator import TINY
from repro.core.estimator import ReasoningEstimator
from repro.data.datasets import build_scope_data
from repro.kernels.decode_attention import KernelType
from repro.models import model as M
from repro.serving import sampler
from repro.serving.kv_pool import KVPool
from repro.serving.scheduler import BucketConfig, MicrobatchScheduler

SPANS = ("scope.prepare", "scope.retrieve", "scope.cache_probe",
         "scope.serialize", "scope.pump", "scope.open", "scope.sync",
         "scope.boundary", "scope.admit", "scope.launch", "scope.parse",
         "scope.finalize", "scope.decide")
# span -> the span it runs inside
PARENT = {"scope.retrieve": "scope.prepare",
          "scope.cache_probe": "scope.prepare",
          "scope.serialize": "scope.prepare",
          "scope.open": "scope.pump", "scope.sync": "scope.pump",
          "scope.boundary": "scope.pump", "scope.admit": "scope.pump",
          "scope.launch": "scope.pump", "scope.parse": "scope.pump"}


class RecordingEstimator(ReasoningEstimator):
    """Keeps every slot state it opens, live or retired."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.runs = []

    def open_slots(self, *a, **kw):
        run = super().open_slots(*a, **kw)
        self.runs.append(run)
        return run


@pytest.fixture(scope="module")
def tiny_params():
    return M.init_params(jax.random.PRNGKey(0), TINY)


@pytest.fixture(scope="module")
def stream_setup(tiny_params, world, retriever, library):
    data = build_scope_data(world, n_queries=40, seed=9)
    queries = [data.queries[int(q)] for q in data.test_qids[:6]]

    def engine():
        est = RecordingEstimator(TINY, tiny_params, max_new_tokens=6)
        return est, ScopeEngine.build(EngineConfig(
            estimator=est, retriever=retriever, library=library,
            models_meta={m: world.models[m] for m in data.models},
            refill=True, segment_len=3, kv_page_size=8))

    # one query fills most of an 8-slot state; later ones queue and ride
    # in as refills; empty requests advance the live state
    ticks = [queries[:1], queries[1:3], [], queries[3:6], [], queries[:1]]
    return engine, ticks


def _scheduler():
    return MicrobatchScheduler(BucketConfig(batch_sizes=(8,)))


def _host_spans(pb: Path):
    """(name, start, end, line) of every ``scope.*`` host event."""
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/device:"):
            continue
        for li, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     (plane.name, li)) for e in line.events
                    if e.name.startswith("scope.")]
    return out


def _inside(child, parents):
    return any(p[3] == child[3] and p[1] <= child[1] and child[2] <= p[2]
               for p in parents)


def test_serve_path_writes_every_span_nested(stream_setup, tmp_path):
    make, ticks = stream_setup
    _, engine = make()
    # compile outside the trace, then trace a second stream
    list(engine.predict_stream(iter([RouteRequest(t) for t in ticks]),
                               scheduler=_scheduler()))
    _, engine = make()
    pol = FixedAlphaPolicy(0.6)
    with jax.profiler.trace(str(tmp_path)):
        for pool in engine.predict_stream(
                iter([RouteRequest(t) for t in ticks]),
                scheduler=_scheduler()):
            if len(pool.p_hat):
                engine.decide(pool, pol)
    pbs = sorted(tmp_path.glob("**/*.xplane.pb"))
    assert pbs, "the profiler wrote no trace"
    spans = _host_spans(pbs[-1])
    by_name = {n: [s for s in spans if s[0] == n] for n in SPANS}
    assert [n for n in SPANS if not by_name[n]] == []
    assert {s[0] for s in spans} == set(SPANS)
    for child, parent in PARENT.items():
        for s in by_name[child]:
            assert _inside(s, by_name[parent]), (child, parent)
    for s in by_name["scope.admit"] + by_name["scope.launch"]:
        # at a boundary, or opening a state: never outside a pump
        assert _inside(s, by_name["scope.pump"])
    assert any(_inside(s, by_name["scope.boundary"])
               for s in by_name["scope.launch"])
    # every non-empty request is prepared once, and only those
    assert len(by_name["scope.prepare"]) == sum(1 for t in ticks if t)


def _op_names(text: str):
    return set(re.findall(r'op_name="([^"]*)"', text))


def _paged_args(params, b=4, width=16, steps=3):
    """Arguments of the two paged decode executables at a tiny size."""
    tokens = np.full((b, width), 5, np.int32)
    state = sampler.prefill_state(
        params, TINY, tokens, max_new_tokens=6,
        kv_pool=KVPool(n_pages=b * 4, page_size=8),
        kv_kernel=KernelType.XLA)
    pg = state.paged
    head = (params, TINY, state.last_logits, state.caches,
            jax.random.PRNGKey(0), steps, 0.0, True, pg.spec,
            pg.device_table(), state.positions, state.done)
    npg = -(-width // pg.page_size)
    refill = (jnp.arange(b, dtype=jnp.int32), jnp.asarray(tokens),
              jnp.full((b,), width, jnp.int32),
              jnp.zeros((b * npg,), jnp.int32))
    return {"_paged_scan_decode": head,
            "_paged_refill_scan_decode": head + refill}


@pytest.mark.parametrize("name,scopes", [
    ("_paged_scan_decode", ("decode", "sample", "embed", "attn", "mlp",
                            "lm_head")),
    ("_paged_refill_scan_decode", ("prefill", "decode", "sample", "embed",
                                   "attn", "mlp", "lm_head")),
])
def test_decode_executables_carry_named_scopes(tiny_params, name, scopes):
    fn = getattr(sampler, name)
    lowered = fn.lower(*_paged_args(tiny_params)[name])
    compiled = _op_names(lowered.compile().as_text())
    lowered_text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert any(f"/{scope}/" in n for n in compiled), (name, scope)
        # a scan body's locations name its path from the body down
        assert re.search(rf'loc\("([^"]*/)?{scope}/', lowered_text), \
            (name, scope)
    # attention and the MLP sit inside a decode step; the prompt's own
    # attention inside the prefill
    assert any("/decode/" in n and "/attn/" in n for n in compiled)
    assert any("/decode/" in n and "/mlp/" in n for n in compiled)
    if "prefill" in scopes:
        assert any("/prefill/" in n and "/attn/" in n for n in compiled)
        assert not any("/prefill/" in n and "/decode/" in n
                       for n in compiled)


def _instructions(hlo: str):
    """A compiled module's instructions without metadata or numbering."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    return [re.sub(r"\.\d+\b", "", ln) for ln in text.splitlines()
            if ln.startswith((" ", "%", "ENTRY", "}"))]


def test_named_scopes_change_metadata_only(tiny_params, monkeypatch):
    """The same executable traced without any named scope compiles to the
    same instructions and fusions, and decodes bit-identically."""
    args = _paged_args(tiny_params)["_paged_scan_decode"]
    static = (1, 5, 6, 7, 8)
    body = sampler._paged_scan_decode.__wrapped__
    scoped = jax.jit(body, static_argnums=static)
    scoped_text = scoped.lower(*args).compile().as_text()
    scoped_out = scoped(*args)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    # a new function object, so the trace is not taken from jit's cache
    plain = jax.jit(functools.wraps(body)(lambda *a: body(*a)),
                    static_argnums=static)
    plain_text = plain.lower(*args).compile().as_text()
    plain_out = plain(*args)
    assert "/decode/" in scoped_text and "/decode/" not in plain_text
    assert "fusion" in scoped_text
    assert _instructions(scoped_text) == _instructions(plain_text)
    jax.tree.map(np.testing.assert_array_equal, scoped_out, plain_out)


def _bucket(n: int, b: int = 8) -> int:
    """The rows a prefill admitting n of b slots computes: the smallest of
    b/4, b/2 and b that holds them."""
    return min(r for r in (b // 4, b // 2, b) if r >= n)


def _stream_counting(make, ticks, monkeypatch):
    """Run the stream; count prefill-bearing launches and the rows their
    buckets compute, and snapshot the stats against the live runs after
    every request."""
    est, engine = make()
    launches = {"n": 0, "rows": 0, "by_rows": {}}
    decode_segment = sampler.decode_segment

    def counted_segment(params, cfg, state, steps, **kw):
        if kw.get("refill") is not None:
            rows = _bucket(int(np.sum(kw["refill"][0])), state.batch)
            launches["n"] += 1
            launches["rows"] += rows
            launches["by_rows"][rows] = launches["by_rows"].get(rows, 0) + 1
        return decode_segment(params, cfg, state, steps, **kw)

    monkeypatch.setattr(sampler, "decode_segment", counted_segment)
    sched = _scheduler()
    snapshots = []
    for _ in engine.predict_stream(iter([RouteRequest(t) for t in ticks]),
                                   scheduler=sched):
        st = sched.stats
        snapshots.append(((st.slot_steps_total, st.slot_steps_active,
                           st.prefill_rows),
                          (sum(r.slot_steps_total for r in est.runs),
                           sum(r.slot_steps_active for r in est.runs),
                           sum(r.prefill_rows for r in est.runs))))
    return est, sched, launches, snapshots


def test_prefill_rows_count_every_prefill_bearing_launch(stream_setup,
                                                          monkeypatch):
    make, ticks = stream_setup
    est, sched, launches, _ = _stream_counting(make, ticks, monkeypatch)
    # a paged state opens with nothing prefilled: its opening rows ride
    # its first launch's refill, and later boundaries refill too
    assert len(est.runs) >= 1 and launches["n"] > len(est.runs)
    # each launch computed its row bucket, not the whole batch
    assert sched.stats.prefill_rows == launches["rows"]
    assert sched.stats.as_dict()["prefill_rows"] == launches["rows"]
    by_rows = sched.stats.as_dict()["prefill_launches_by_rows"]
    assert by_rows == launches["by_rows"]
    assert sum(by_rows.values()) == launches["n"]


def test_slot_steps_exact_mid_stream_and_unchanged_at_the_end(
        stream_setup, monkeypatch):
    make, ticks = stream_setup
    est, sched, _, snapshots = _stream_counting(make, ticks, monkeypatch)
    # between two requests the stats hold every run's counters so far,
    # the live run's included
    assert len(snapshots) == len(ticks)
    for got, want in snapshots:
        assert got == want
    assert any(s[0][0] for s in snapshots[:-1])
    # at the end, what folding only at retire gave: every run's total
    st = sched.stats
    assert all(r.finished for r in est.runs)
    assert st.slot_steps_total == sum(r.slot_steps_total for r in est.runs)
    assert st.slot_steps_active == sum(r.slot_steps_active
                                       for r in est.runs)
    assert st.refill_steps_saved == sum(r.refill_steps for r in est.runs)
    assert 0 < st.slot_steps_active <= st.slot_steps_total
