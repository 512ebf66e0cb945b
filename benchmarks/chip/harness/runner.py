"""One run of one cell: set-up, the measured window, the check, the result.

The window drives ``ScopeEngine.predict_stream`` on the continuous-batching
path with the paged KV pool, then ``ScopeEngine.decide`` on every yielded
request.  The engine advances its decode state one segment per request it
pulls, so the harness keeps pulling: when no query is due and work is in
flight it pulls an empty request, and when nothing is in flight it sleeps
until the next arrival.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import math
import sys
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from harness import checks, spec, traffic
from harness.spec import Cell

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# program objects
# ---------------------------------------------------------------------------
def program_config(cfg_file: Dict[str, Any]):
    """The program's ModelConfig for a configuration file, checked against
    the sizes the file states."""
    import dataclasses as dc
    loads = cfg_file["loads"]
    obj = getattr(importlib.import_module(loads["module"]), loads["attr"])
    cfg = obj(loads["arg"]) if "arg" in loads else obj
    cfg = dc.replace(cfg, **cfg_file.get("overrides", {}))
    have = {k: getattr(cfg, k) for k in cfg_file["model"]}
    have["head_dim"] = cfg.resolved_head_dim
    bad = {k: (have[k], v) for k, v in cfg_file["model"].items()
           if have[k] != v}
    if bad:
        raise ValueError(f"program config differs from {cfg_file['name']}: "
                         f"(program, file) {bad}")
    return cfg


def recorder_class():
    from repro.core.estimator import ReasoningEstimator, SlotRun

    class RecordedRun(SlotRun):
        """A slot state that hands the check each pair's prompt and served
        tokens the first time a slot finishes it, and its slot-step
        counters when it retires."""

        recorder = None

        def parse_completed(self, completed):
            served = self.recorder.served
            for row, slot in completed:
                if slot.tag not in served:
                    n = min(self.budget, self.steps_done - slot.start)
                    served[slot.tag] = (
                        list(slot.prompt),
                        self._gen[row, slot.start: slot.start + n].copy())
            return super().parse_completed(completed)

        def account(self, stats):
            self.recorder.retire(self)
            return super().account(stats)

    class Recorder(ReasoningEstimator):
        """The serve-path estimator, opening ``RecordedRun`` states.  It
        holds live states only: a retired one leaves its counters."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.runs: List[Any] = []         # live slot states
            self.retired = [0, 0]               # their slot-step counters
            self.served: Dict[Any, tuple] = {}

        def open_slots(self, tokens, *, lengths=None, tags=None,
                       segment_len: int = 4, horizon=None, rng=None,
                       kv_pool=None, kv_kernel=None):
            if kv_pool is not None and horizon is not None:
                raise ValueError("horizon and kv_pool are mutually "
                                 "exclusive")
            run = RecordedRun(self, tokens, lengths=lengths, tags=tags,
                              segment_len=segment_len, horizon=horizon,
                              rng=rng, kv_pool=kv_pool, kv_kernel=kv_kernel)
            run.recorder = self
            self.runs.append(run)
            return run

        def retire(self, run):
            if run in self.runs:
                self.retired[0] += run.slot_steps_total
                self.retired[1] += run.slot_steps_active
                self.runs.remove(run)

        def slot_steps(self):
            return (self.retired[0] + sum(r.slot_steps_total
                                          for r in self.runs),
                    self.retired[1] + sum(r.slot_steps_active
                                          for r in self.runs))

    return Recorder


def counters(sched, est) -> Dict[str, Any]:
    st = sched.stats
    total, active = est.slot_steps()
    return {"emitted": st.emitted, "real_tokens": st.real_tokens,
            "slots_refilled": st.slots_refilled,
            "microbatches": st.microbatches,
            "slot_steps_total": total, "slot_steps_active": active,
            "ages": list(st.queue_ages)}


def delta(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    """Counters b - a; ``queue_ages`` are those appended in between."""
    out = {k: b[k] - a[k] for k in a if k != "ages"}
    n = out["emitted"]
    out["queue_ages"] = b["ages"][len(b["ages"]) - n:] if n > 0 else []
    return out


@dataclasses.dataclass
class World:
    world: Any
    anchors: Any
    library: Any
    models: List[str]
    meta: Dict[str, Any]


def build_world(seed: int, deploy: Dict[str, Any]) -> World:
    from repro.core.fingerprint import FingerprintLibrary, build_anchor_set
    from repro.data.datasets import stratified_anchors
    from repro.data.worldsim import World as SimWorld
    world = SimWorld(seed=traffic.seed32(seed, 2))
    aset = build_anchor_set(world, stratified_anchors(
        world, n=int(deploy["anchors"]), seed=traffic.seed32(seed, 3)))
    lib = FingerprintLibrary(aset)
    models = [m.name for m in world.pool]           # seen and unseen: M = 11
    for i, m in enumerate(models):
        lib.onboard(world, m, seed=traffic.seed32(seed, 100 + i))
    return World(world, aset, lib, models, {m: world.models[m] for m in models})


class Queries:
    """Distinct queries drawn from the world, in chunks, with unique ids."""

    def __init__(self, w: World, seed: int):
        self.w = w
        self.seed = seed
        self.buf: deque = deque()
        self.next_qid = 0
        self.chunk = 0

    def take(self, n: int) -> List[Any]:
        from repro.data.worldsim import Query
        while len(self.buf) < n:
            self.chunk += 1
            for q in self.w.world.sample_queries(
                    512, seed=traffic.seed32(self.seed, 1000 + self.chunk)):
                self.buf.append(Query(self.next_qid, q.domain, q.difficulty,
                                      q.embedding))
                self.next_qid += 1
        return [self.buf.popleft() for _ in range(n)]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What one run produced, for the metric readers and the check."""
    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    ttd_ms: np.ndarray              # counted queries, time to decision
    intake_ms: np.ndarray           # counted queries, pull - scheduled
    hit_wait_ms: np.ndarray         # requests with no miss: yield - pull
    decided: int                    # counted queries decided by rate_s
    rate_s: float                   # seconds decisions_per_s spans
    attempted: int
    failed: int
    counters: Dict[str, Any]        # program counters over the window
    trace: Optional[Dict[str, Any]] = None      # trace_reduce.reduce
    compiles_in_window: int = 0
    memory_peak_bytes: Optional[int] = None
    device_kind: str = ""

    @property
    def model(self) -> Dict[str, Any]:
        return self.cell.config["model"]

    @property
    def deploy(self) -> Dict[str, Any]:
        return self.cell.config["deployment"]


class Session:
    """Set-up and windows of one cell, reusable across seeds in one process
    (the compiled executables stay warm)."""

    def __init__(self, cell: Cell, t_start: float, *, out_dir=None):
        import jax
        self.jax = jax
        self.cell = cell
        self.t_start = t_start
        self.out_dir = out_dir
        self.cfg = program_config(cell.config)
        self.ref = spec.reference_module(cell.config)
        self.compiles = 0
        self.counting = False

        def on_event(event, duration, **kw):
            if event == COMPILE_EVENT and self.counting:
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    # -- set-up ---------------------------------------------------------
    def build_engine(self):
        from repro.api import EngineConfig, ScopeEngine
        fields = {f.name for f in dataclasses.fields(EngineConfig)}
        knobs = self.cell.config.get("engine", {})
        dropped = sorted(k for k in knobs if k not in fields)
        if dropped:
            say(f"engine settings the program no longer has, dropped: "
                f"{dropped}")
        return ScopeEngine.build(EngineConfig(
            estimator=self.est, retriever=self.retriever,
            library=self.w.library, models_meta=self.w.meta,
            k=int(self.cell.config["deployment"]["top_k"]),
            **{k: v for k, v in knobs.items() if k in fields}))

    def scheduler(self):
        from repro.serving.scheduler import BucketConfig, MicrobatchScheduler
        d = self.cell.config["deployment"]
        sc = self.cell.config.get("scheduler", {})
        return MicrobatchScheduler(
            BucketConfig(batch_sizes=(int(d["slots"]),),
                         prompt_lens=(int(d["prompt_len"]),)), **sc)

    def policy(self):
        from repro.api import policy as pol
        p = dict(self.cell.traffic["policy"])
        kinds = {"fixed_alpha": pol.FixedAlphaPolicy}
        return kinds[p.pop("kind")](**p)

    def setup(self, seed: int):
        """Weights, world, engine and warm-up for ``seed``."""
        jax = self.jax
        from repro.core.retrieval import AnchorRetriever
        from repro.models import model as M
        m = self.cell.config["model"]
        d = self.cell.config["deployment"]
        # the previous seed's weights and state go before new ones are made
        self.params = self.est = self.engine = self.retriever = None
        self.last_pools = {}
        gc.collect()
        want = jax.eval_shape(lambda k: M.init_params(k, self.cfg),
                              jax.random.PRNGKey(0))
        params = self.ref.make_params(traffic.seed32(seed, 4), m)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
            raise ValueError("the reference's weight layout differs from "
                             "the program's")
        jax.block_until_ready(params)
        self.params = params
        self.w = build_world(seed, d)
        Recorder = recorder_class()
        self.est = Recorder(self.cfg, params,
                            max_new_tokens=int(d["decode_budget"]))
        self.retriever = AnchorRetriever(self.w.anchors,
                                         **d.get("retriever", {}))
        self.engine = self.build_engine()
        self.queries = Queries(self.w, seed)
        self.pol = self.policy()
        self.warm()
        self.hot = self.fill_hot()

    def warm(self):
        """Every shape the window uses: retrieval for 1..cap queries, a
        state opened, segments with and without a refill, a retire."""
        from repro.api import RouteRequest
        cap = int(self.cell.traffic["max_queries_per_request"])
        k = int(self.cell.config["deployment"]["top_k"])
        for q in range(1, cap + 1):
            self.retriever.retrieve(np.ones((q, self.w.anchors.embeddings
                                             .shape[1]), np.float32), k)
        models = self.w.models
        reqs = [RouteRequest(self.queries.take(1), models=models),
                RouteRequest(self.queries.take(2), models=models)]
        reqs += [RouteRequest([], models=models)] * 4
        for pool in self.engine.predict_stream(iter(reqs),
                                               scheduler=self.scheduler()):
            if len(pool.p_hat):
                self.engine.decide(pool, self.pol)

    def fill_hot(self) -> List[Any]:
        """Put the hot set's pairs in the prediction cache (set-up the
        traffic needs), through the same stream path."""
        from repro.api import RouteRequest
        n = traffic.hot_set_size(self.cell.traffic)
        if not n:
            return []
        hot = self.queries.take(n)
        cap = int(self.cell.traffic["max_queries_per_request"])
        reqs = [RouteRequest(hot[i: i + cap], models=self.w.models)
                for i in range(0, n, cap)]
        pools = list(self.engine.predict_stream(iter(reqs),
                                                scheduler=self.scheduler()))
        if sum(p.cache_misses for p in pools) != n * len(self.w.models):
            raise RuntimeError("hot-set fill did not run every pair")
        return hot

    # -- the window -----------------------------------------------------
    def window(self, seed: int, seconds: float, trace: bool) -> Run:
        jax = self.jax
        from repro.api import RouteRequest
        TA = jax.profiler.TraceAnnotation
        sched_cfg = traffic.schedule(self.cell.traffic, self.cell.params,
                                     seconds, seed)
        cap = int(self.cell.traffic["max_queries_per_request"])
        models = self.w.models
        closed = sched_cfg.outstanding is not None
        n_arr = len(sched_cfg.arrivals)
        # queries for every open-loop arrival, drawn before the window
        if closed:
            qs: List[Any] = []
        else:
            n_new = int((sched_cfg.slots < 0).sum())
            new = iter(self.queries.take(n_new))
            qs = [self.hot[s] if s >= 0 else next(new)
                  for s in sched_cfg.slots]
        t_sched = list(sched_cfg.arrivals)
        t_pull: List[float] = [math.nan] * n_arr
        t_dec: List[float] = [math.nan] * n_arr
        ok: List[bool] = [False] * n_arr
        pulled: deque = deque()         # per request: arrival indices
        req_pull: deque = deque()
        hit_wait: List[float] = []
        pools: Dict[int, tuple] = {}
        sched = self.scheduler()
        st = {"next": 0, "inflight": 0, "counted_done": 0,
              "span_open": False}
        snaps: Dict[str, Any] = {}
        n_counted = sched_cfg.n_counted
        out_dir = None
        if trace:
            out_dir = self.out_dir / f"trace-{self.cell.name}-{seed}"
        clock = time.perf_counter
        span: Dict[str, Any] = {}

        def close_span(now):
            if st["span_open"] and now >= seconds:
                span["traced"].__exit__(None, None, None)
                st["span_open"] = False

        def requests():
            while True:
                now = clock() - t_open
                close_span(now)
                if closed:
                    if now >= seconds:
                        return
                    n = min(cap, sched_cfg.outstanding - st["inflight"])
                    idx = []
                    if n > 0:
                        with TA("bench.pull"):
                            for q in self.queries.take(n):
                                qs.append(q)
                                t_sched.append(now)
                                t_pull.append(math.nan)
                                t_dec.append(math.nan)
                                ok.append(False)
                                idx.append(len(qs) - 1)
                else:
                    if now >= seconds and st["counted_done"] >= n_counted:
                        return
                    idx = traffic.due(sched_cfg.arrivals, st["next"], now, cap)
                    if not idx and st["inflight"] == 0:
                        nxt = st["next"]
                        if nxt >= n_arr:
                            return
                        with TA("bench.wait"):
                            time.sleep(max(0.0, min(
                                sched_cfg.arrivals[nxt] - now, 0.05)))
                        continue
                    st["next"] += len(idx)
                t = clock() - t_open
                for i in idx:
                    t_pull[i] = t
                pulled.append(idx)
                req_pull.append(t)
                st["inflight"] += len(idx)
                yield RouteRequest([qs[i] for i in idx], models=models)

        self.compiles = 0
        # a full collection inside the window would scan every object
        # set-up made; frozen, they are left out of it
        gc.collect()
        gc.freeze()
        if trace:
            out_dir.mkdir(parents=True, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(out_dir), profiler_options=opts)
        self.counting = True
        t_open = clock()
        snaps["open"] = counters(sched, self.est)
        if trace:
            # made after the profiler starts, or it records nothing
            span["traced"] = TA("bench.traced")
            span["traced"].__enter__()
            st["span_open"] = True
        try:
            for pool in self.engine.predict_stream(requests(),
                                                   scheduler=sched):
                idx = pulled.popleft()
                tp = req_pull.popleft()
                if not idx:
                    continue
                t_y = clock() - t_open
                if pool.cache_misses == 0:
                    hit_wait.append(t_y - tp)
                with TA("bench.decide"):
                    dec = self.engine.decide(pool, self.pol)
                t = clock() - t_open
                good = pool.status is None or bool((pool.status == 0).all())
                for r, i in enumerate(idx):
                    t_dec[i] = t
                    ok[i] = good
                    pools[i] = (pool, r, int(dec.choices[r]))
                    if not closed and i < n_counted:
                        st["counted_done"] += 1
                st["inflight"] -= len(idx)
                if snaps.get("close") is None and t >= seconds:
                    snaps["close"] = counters(sched, self.est)
                close_span(t)
        finally:
            close_span(math.inf)
            self.counting = False
            gc.unfreeze()
            if trace:
                jax.profiler.stop_trace()
        snaps.setdefault("close", counters(sched, self.est))
        faults = sched.stats.as_dict()["faults"]

        t_sched_a = np.asarray(t_sched)
        t_pull_a = np.asarray(t_pull)
        t_dec_a = np.asarray(t_dec)
        if closed:
            # the loop sends nothing once the window's time is up and waits
            # for what it sent: every query sent counts, over the time until
            # the last of them was decided.  (Cut at the window's end, the
            # count moved by a whole request of 12 with the phase of the
            # last one against the close.)
            counted = np.flatnonzero(t_pull_a < seconds)
            t_done = t_dec_a[counted]
            decided = int(np.isfinite(t_done).sum())
            rate_s = float(np.nanmax(t_done)) if decided else seconds
        else:
            counted = np.arange(n_counted)
            decided = int((t_dec_a[:n_counted] < seconds).sum())
            rate_s = seconds
        ok_a = np.asarray(ok)
        failed = int((~ok_a[counted]).sum()) + int(faults["unexpected"])
        stats = jax.devices()[0].memory_stats() or {}
        run = Run(
            cell=self.cell, seed=seed, seconds=seconds,
            setup_s=t_open - self.t_start,
            ttd_ms=(t_dec_a[counted] - t_sched_a[counted]) * 1e3,
            intake_ms=(t_pull_a[counted] - t_sched_a[counted]) * 1e3,
            hit_wait_ms=np.asarray(hit_wait) * 1e3,
            decided=decided, rate_s=rate_s, attempted=len(counted),
            failed=failed,
            counters=delta(snaps["open"], snaps["close"]),
            compiles_in_window=self.compiles,
            device_kind=jax.devices()[0].device_kind,
            memory_peak_bytes=stats.get("peak_bytes_in_use"))
        self.last_pools = pools
        self.last_qs = qs
        self.last_counted = counted
        self.last_failed = failed
        return run

    # -- after the window ------------------------------------------------
    def release(self) -> None:
        """Free the program's state (slot states and their KV pools)."""
        self.est.runs.clear()
        gc.collect()

    def check(self, seed: int, control: str = "none") -> Dict[str, Any]:
        return checks.check(self, seed, control=control)
