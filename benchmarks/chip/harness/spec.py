"""What a run reads from ``BENCHMARK.json`` and the benchmark's data files.

Everything that belongs to one configuration, traffic mix, cell or metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json     model sizes, source, deployment, engine knobs
  traffic/<traffic>.json    arrival process, popularity, fan-out, policy
  cells/<workload>.json     the cell's own numbers (an arrival rate)
  metrics/<metric>.py       one reader per metric: ``read(record)``
  references/<name>.py      a family's plain reference and weight maker

so a later change adds a configuration, a mix or a metric by adding files
and entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # configs/<config>.json
    traffic: Dict[str, Any]         # traffic/<traffic>.json
    params: Dict[str, Any]          # cells/<workload>.json ({} if none)
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def _reports(metric: Dict[str, Any], workload: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or workload in cells


def load_cell(workload: str, bench: Optional[Dict[str, Any]] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell named ``workload``, with its files and its metrics."""
    if bench is None:
        bench = load_json(bench_dir.parents[1] / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    reported = {m["name"] for m in e2e}
    # a per-layer metric without a cell list follows its end-to-end metric
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    cell_file = bench_dir / "cells" / f"{workload}.json"
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json(bench_dir / "configs" / f"{w['config']}.json"),
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        params=load_json(cell_file) if cell_file.exists() else {},
        end_to_end=e2e, per_layer=layer)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR
                  ) -> Callable[[Any], Optional[float]]:
    """``read(record) -> float | None`` of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    return load_module(path, "metric_" + name.replace(".", "_")).read


def reference_module(config: Dict[str, Any], bench_dir: Path = BENCH_DIR):
    name = config["reference"]
    return load_module(bench_dir / "references" / f"{name}.py",
                       "reference_" + name)


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no peaks in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
