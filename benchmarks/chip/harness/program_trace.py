"""The program's own spans and named scopes, read from the same profiler
trace that ``trace_reduce`` reduces, as additions to it.

The serve path writes ``scope.*`` host spans with
``jax.profiler.TraceAnnotation`` and names the estimator's layers with
``jax.named_scope`` (``prefill``, ``decode``, ``sample``, ``embed``,
``lm_head``, ``attn``, ``mlp``).  ``flatten`` returns what
``trace_reduce.flatten`` returns, with the ``scope.*`` host events added to
the host lines (four fields each, as every event) and a top-level
``scopes`` table ``{module: {op short name: op_name path}}``.

On a TPU the path is the ``tf_op`` stat of each operation's event metadata
(``jit(f)/while/body/closed_call/decode/attn/dot_general:``), keyed by its
``program_id``, which is also the number in the executable's name on the
``XLA Modules`` line.  ``jax.profiler.ProfileData`` exposes event stats
only, not event-metadata stats, so ``op_paths`` reads them from the
``.xplane.pb`` wire format.  A CPU trace carries no such stat: its table
is empty and every operation counts as ``other``.

``reduce`` returns ``trace_reduce.reduce``'s keys with the same values,
except that an idle gap is named by the innermost ``bench.*`` or
``scope.*`` span over its middle (its duration is the same), and adds

  spans              {span name: [seconds, ...]}, spans starting in the window
  host_bound_idle_s  device-idle seconds under the union of ``scope.*`` spans
  scope_s            {module: {scope path: device self-seconds}}; a path is
                     the named scopes an op sits in, outer first
                     (``decode/attn``), or ``other`` when it sits in none
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from harness import trace_reduce as tr

SPAN_PREFIX = "scope."
SCOPES = frozenset({"prefill", "decode", "sample", "embed", "lm_head",
                    "attn", "mlp"})
OTHER = "other"
_PROGRAM = re.compile(r"\((\d+)\)$")


# ---------------------------------------------------------------------------
# the op_name path of every operation (TPU: event-metadata ``tf_op``)
# ---------------------------------------------------------------------------
def _varint(buf, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf, start: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for varints,
    a (start, end) span for length-delimited fields."""
    pos = start
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            val, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            val, pos = (pos, pos + n), pos + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            val, pos = None, pos + n
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]: span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value of one protobuf map entry (field 2)."""
    return next((v for n, v in _fields(buf, *span) if n == 2), None)


def op_paths(data: bytes) -> List[Tuple[int, str, str]]:
    """(program id, op short name, op_name path) of every operation whose
    event metadata on a ``/device:`` plane carries ``tf_op``.

    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map to
    XEventMetadata: name = 2, stats = 5), stat_metadata = 5 (map to
    XStatMetadata: id = 1, name = 2); XStat: metadata_id = 1, int64 = 4,
    uint64 = 3, str = 5, ref (a stat-metadata id) = 7.  The events
    themselves (XPlane.lines = 3) are skipped unread."""
    buf = memoryview(data)
    out = []
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for n, v in _fields(buf, *plane):
            if n == 2:
                name = _text(buf, v)
            elif n == 4:
                metas.append(_map_value(buf, v))
            elif n == 5:
                sm = dict(_fields(buf, *_map_value(buf, v)))
                stat_names[sm.get(1, 0)] = _text(buf, sm.get(2, (0, 0)))
        if not name.startswith("/device:"):
            continue
        for meta in metas:
            op, program, path = "", None, None
            for n, v in _fields(buf, *meta):
                if n == 2:
                    op = _text(buf, v)
                elif n == 5:
                    st = dict(_fields(buf, *v))
                    key = stat_names.get(st.get(1))
                    if key == "program_id":
                        program = st.get(4, st.get(3))
                    elif key == "tf_op":
                        path = (_text(buf, st[5]) if 5 in st
                                else stat_names.get(st.get(7), ""))
            if path and program is not None:
                out.append((program, tr.short_name(op),
                            path.rsplit(":", 1)[0]))
    return out


def scope_table(trace: Dict, paths: Sequence[Tuple[int, str, str]]
                ) -> Dict[str, Dict[str, str]]:
    """``{module: {op short name: path}}``, the module named by the
    executable whose ``XLA Modules`` events carry the program id."""
    names = {}
    for plane in tr.device_planes(trace):
        ln = tr._line(plane, tr.MODULES_LINE)
        for e in (ln["events"] if ln is not None else []):
            m = _PROGRAM.search(e[0])
            if m:
                names[int(m.group(1))] = tr.base_name(e[0])
    table: Dict[str, Dict[str, str]] = {}
    for program, op, path in paths:
        if program in names:
            table.setdefault(names[program], {})[op] = path
    return table


def flatten(path: str) -> Dict:
    """``trace_reduce.flatten`` plus the ``scope.*`` host events and the
    ``scopes`` table."""
    import jax
    trace = tr.flatten(path)
    planes = {p["name"]: p for p in trace["planes"]}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [[e.name, float(e.start_ns), float(e.duration_ns), ""]
                   for e in line.events if e.name.startswith(SPAN_PREFIX)]
            if not evs:
                continue
            lines = planes[plane.name]["lines"]
            have = next((ln for ln in lines if ln["name"] == line.name),
                        None)
            if have is None:
                lines.append({"name": line.name, "events": evs})
            else:
                have["events"] += evs
    with open(path, "rb") as fh:
        trace["scopes"] = scope_table(trace, op_paths(fh.read()))
    return trace


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------
def scope_key(path: str) -> str:
    """``jit(f)/while/body/closed_call/decode/attn/dot_general`` ->
    ``decode/attn``: the program's named scopes on the path, outer first."""
    parts = [p for p in path.split("/") if p in SCOPES]
    return "/".join(parts) if parts else OTHER


def span_seconds(spans, t0: float, t1: float) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for name, a, b in spans:
        if t0 <= a < t1:
            out.setdefault(name, []).append((b - a) / 1e9)
    return out


def idle(busy_iv, t0: float, t1: float) -> List[tr.Interval]:
    """The window less the device's busy intervals."""
    gaps, cur = [], t0
    for a, b in busy_iv:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def overlap(xs: List[tr.Interval], ys: List[tr.Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def self_times(evs: Sequence[Sequence]) -> Iterator[Tuple[Sequence, float]]:
    """(op event, self ns): its time less that of the ops inside it."""
    stack: List[List] = []          # [event, end, self_ns]
    for e in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= e[1]:
            top = stack.pop()
            yield top[0], top[2]
        if stack:
            stack[-1][2] -= e[2]
        stack.append([e, e[1] + e[2], e[2]])
    while stack:
        top = stack.pop()
        yield top[0], top[2]


def op_scopes(trace: Dict, t0: float, t1: float
              ) -> Iterator[Tuple[str, str, str, float]]:
    """(module, scope path, op short name, self ns) of each op of the first
    device plane that starts in [t0, t1).  An op's module is the
    executable run it starts in (or its own, without a module line)."""
    planes = tr.device_planes(trace)
    if not planes:
        return
    p = planes[0]
    table = trace.get("scopes", {})
    ln = tr._line(p, tr.MODULES_LINE)
    runs = sorted((e[1], e[1] + e[2], tr.base_name(e[0]))
                  for e in (ln["events"] if ln is not None else []))
    starts = [r[0] for r in runs]
    ops = [e for e in tr.op_events(p) if t0 <= e[1] < t1]
    for e, own in self_times(ops):
        if runs:
            i = bisect.bisect_right(starts, e[1]) - 1
            if i < 0 or e[1] >= runs[i][1]:
                continue
            module = runs[i][2]
        else:
            module = tr.base_name(e[3])
        if not module:
            continue
        op = tr.short_name(e[0])
        path = table.get(module, {}).get(op)
        yield module, scope_key(path) if path else OTHER, op, own


def scope_seconds(trace: Dict, t0: float, t1: float
                  ) -> Dict[str, Dict[str, float]]:
    """Device self-seconds per module and scope path."""
    out: Dict[str, Dict[str, float]] = {}
    for module, key, _, own in op_scopes(trace, t0, t1):
        per = out.setdefault(module, {})
        per[key] = per.get(key, 0.0) + own / 1e9
    return out


def reduce(trace: Dict) -> Dict:
    """``trace_reduce.reduce`` and the program's spans and scopes."""
    out = tr.reduce(trace)
    t0, t1 = tr.window(trace)
    _, busy_iv = tr.busy(trace, t0, t1)
    prog = tr.host_spans(trace, SPAN_PREFIX)
    out["idle_gaps"] = tr.idle_gaps(busy_iv, tr.host_spans(trace) + prog,
                                    t0, t1)
    out["spans"] = span_seconds(prog, t0, t1)
    covered = tr.union(tr.clip([(s[0], s[1], s[2] - s[1]) for s in prog],
                               t0, t1))
    out["host_bound_idle_s"] = overlap(idle(busy_iv, t0, t1), covered) / 1e9
    out["scope_s"] = scope_seconds(trace, t0, t1)
    return out


# ---------------------------------------------------------------------------
# what the per-layer metrics read
# ---------------------------------------------------------------------------
def host_bound_idle_share(red) -> Optional[float]:
    """Device-idle time under a ``scope.*`` span over the window (%)."""
    if not red or not red.get("spans"):
        return None
    return 100.0 * red["host_bound_idle_s"] / red["window_s"]


def span_ms_p95(red, name: str) -> Optional[float]:
    """95th percentile of the ``name`` spans' durations (ms)."""
    got = (red or {}).get("spans", {}).get(name)
    return 1e3 * float(np.percentile(got, 95)) if got else None


def scope_ms(red, module: str, scope: str, steps: int = 1
             ) -> Optional[float]:
    """Device self-time under the named scope ``scope`` in the launches
    of ``module``, per launch and per ``steps`` (ms)."""
    red = red or {}
    m = red.get("modules", {}).get(module)
    got = [s for k, s in red.get("scope_s", {}).get(module, {}).items()
           if scope in k.split("/")]
    if not m or not m["count"] or not got:
        return None
    return 1e3 * sum(got) / (m["count"] * steps)
