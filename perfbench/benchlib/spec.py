"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root lists configurations,
workloads (cells) and metrics.  Everything that belongs to one of them
is a file of its own under ``perfbench/``:

- ``configs/<config>.json``: the configuration (named by its entry);
- ``traffic/<traffic>.json``: the traffic mix;
- ``limits/<workload>.json``: the limits of the numbers ``correct``
  compares, with the readings they were set from;
- ``metrics/<metric>.py``: the reader of a per-layer metric, a function
  ``read(run)`` that returns a number or None."""
from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass
from typing import Callable, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(LookupError):
    """A name that BENCHMARK.json or the benchmark's files do not have."""


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _read_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(bench: dict, workload: str, root: pathlib.Path = ROOT) -> Cell:
    try:
        wl = next(w for w in bench["workloads"] if w["name"] == workload)
    except StopIteration:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json") \
            from None
    try:
        cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    except StopIteration:
        raise SpecError(f"no config {wl['config']!r} in BENCHMARK.json") \
            from None
    bdir = root / "perfbench"
    return Cell(
        name=workload, chips=int(wl["chips"]), config_name=cfg["name"],
        config=_read_json(root / cfg["file"]), traffic_name=wl["traffic"],
        traffic=_read_json(bdir / "traffic" / f"{wl['traffic']}.json"),
        limits=_read_json(bdir / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def metric_reader(name: str, root: pathlib.Path = ROOT
                  ) -> Callable[[object], Optional[float]]:
    path = root / "perfbench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
