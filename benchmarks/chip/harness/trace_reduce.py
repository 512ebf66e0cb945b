"""From a profiler trace to the numbers the per-layer metrics read.

A trace is first flattened to plain data (``flatten``):

  {"planes": [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops",
                          "events": [[name, start_ns, dur_ns, module], ...]}]}]}

so the reduction below is tested on a written fixture and needs no chip.
Device planes are the ones named ``/device:...``; an executable's time is
read from their ``XLA Modules`` line (or, without it, from the ops grouped
by their module) and an operation's from ``XLA Ops``.  The device is busy
while an executable runs: inside one, the op events leave gaps where the
chip waits on memory, so their union would undercount.  Host spans
are the events named ``bench.*`` that the harness writes with
``jax.profiler.TraceAnnotation``; ``bench.traced`` bounds the window.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"


def flatten(path: str) -> Dict:
    """Read an ``.xplane.pb`` into the plain form above, keeping only what
    the reduction reads: the device planes' op and module lines and the
    harness's host spans."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        names = [ln.name for ln in plane.lines]
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            # an op's module is read only where no module line exists
            want_module = (device and line.name == OPS_LINE
                           and MODULES_LINE not in names)
            evs = []
            for e in line.events:
                name = e.name
                if not device and not name.startswith("bench."):
                    continue
                module = ""
                if want_module:
                    module = next((str(v) for k, v in e.stats
                                   if k == "hlo_module"), "")
                evs.append([name, float(e.start_ns), float(e.duration_ns),
                            module])
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: Dict) -> List[Dict]:
    """The chips: device planes with an op or module line (a TPU trace
    also holds device planes of other kinds, with neither)."""
    return [p for p in trace["planes"] if p["name"].startswith("/device:")
            and any(ln["name"] in (OPS_LINE, MODULES_LINE)
                    for ln in p["lines"])]


def _line(plane: Dict, name: str) -> Optional[Dict]:
    return next((ln for ln in plane["lines"] if ln["name"] == name), None)


def host_spans(trace: Dict, prefix: str = "bench.") -> List[Tuple[str, float,
                                                                 float]]:
    """(name, start_ns, end_ns) of the harness's host spans."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            out += [(e[0], e[1], e[1] + e[2]) for e in ln["events"]
                    if e[0].startswith(prefix)]
    return sorted(out, key=lambda s: s[1])


def window(trace: Dict) -> Interval:
    """The traced window: the harness's ``bench.traced`` span."""
    spans = [s for s in host_spans(trace) if s[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    return spans[0][1], spans[0][2]


def clip(events: Iterable[Sequence], t0: float, t1: float
         ) -> List[Interval]:
    out = []
    for e in events:
        a, b = max(e[1], t0), min(e[1] + e[2], t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def op_events(plane: Dict) -> List[Sequence]:
    ln = _line(plane, OPS_LINE)
    return ln["events"] if ln is not None else []


def busy_events(plane: Dict) -> List[Sequence]:
    """What occupies the device: its executables' runs where the module
    line exists (operations leave gaps inside a run while they wait on
    memory), else its operations."""
    ln = _line(plane, MODULES_LINE)
    return ln["events"] if ln is not None else op_events(plane)


def busy(trace: Dict, t0: float, t1: float) -> Tuple[float, List[Interval]]:
    """Seconds in which the device ran something, averaged over the
    device planes, and the first plane's merged busy intervals (ns)."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("trace has no device plane")
    per, first = [], None
    for p in planes:
        u = union(clip(busy_events(p), t0, t1))
        per.append(total(u) / 1e9)
        first = u if first is None else first
    return sum(per) / len(per), first


def base_name(name: str) -> str:
    """``jit__paged_scan_decode(42)`` -> ``jit__paged_scan_decode``."""
    return re.sub(r"\(.*\)$", "", name).strip()


def modules(trace: Dict, t0: float, t1: float) -> Dict[str, Dict[str, float]]:
    """Per executable: launches and device seconds inside [t0, t1], from
    the first device plane.  A launch counts where it starts."""
    planes = device_planes(trace)
    if not planes:
        return {}
    p = planes[0]
    out: Dict[str, Dict[str, float]] = {}
    ln = _line(p, MODULES_LINE)
    if ln is not None:
        for name, start, dur, _ in ln["events"]:
            if t0 <= start < t1:
                m = out.setdefault(base_name(name), {"count": 0, "seconds": 0.0})
                m["count"] += 1
                m["seconds"] += dur / 1e9
        return out
    # no module line: group ops by module, one launch per contiguous run
    last = None
    for name, start, dur, module in sorted(op_events(p), key=lambda e: e[1]):
        if not (t0 <= start < t1) or not module:
            continue
        m = out.setdefault(base_name(module), {"count": 0, "seconds": 0.0})
        m["seconds"] += dur / 1e9
        if module != last:
            m["count"] += 1
        last = module
    return out


def short_name(op: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return op.split(" = ", 1)[0].strip()


def top_ops(trace: Dict, t0: float, t1: float, n: int = 10
            ) -> List[List]:
    """The n operations (by name) that took most device time, each by its
    self time: a loop's time less that of the operations inside it."""
    planes = device_planes(trace)
    if not planes:
        return []
    evs = sorted((e for e in op_events(planes[0]) if t0 <= e[1] < t1),
                 key=lambda e: (e[1], -e[2]))
    acc: Dict[str, float] = {}
    stack: List[List] = []          # [name, end, self_ns]

    def pop():
        name, _, own = stack.pop()
        acc[name] = acc.get(name, 0.0) + own / 1e9

    for name, start, dur, _ in evs:
        while stack and stack[-1][1] <= start:
            pop()
        if stack:
            stack[-1][2] -= dur
        stack.append([short_name(name), start + dur, dur])
    while stack:
        pop()
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(busy_iv: List[Interval], spans: List[Tuple[str, float, float]],
              t0: float, t1: float, n: int = 10) -> List[List]:
    """The n longest device-idle gaps in [t0, t1], each named by the
    innermost harness span covering its middle (``engine`` when the host
    was inside the program, between the harness's own spans)."""
    gaps, cur = [], t0
    for a, b in busy_iv:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        inner = [s for s in spans if s[1] <= mid < s[2] and s[0] != WINDOW_SPAN]
        label = min(inner, key=lambda s: s[2] - s[1])[0] if inner else "engine"
        out.append([label, (b - a) / 1e9])
    return out


def reduce(trace: Dict) -> Dict:
    """Everything the readers and the result line take from one trace."""
    t0, t1 = window(trace)
    busy_s, iv = busy(trace, t0, t1)
    return {"window_s": (t1 - t0) / 1e9, "busy_s": busy_s,
            "modules": modules(trace, t0, t1),
            "device_ops": top_ops(trace, t0, t1),
            "idle_gaps": idle_gaps(iv, host_spans(trace), t0, t1)}
