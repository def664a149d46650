"""What a run measures, found by name in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under ``bench/``:

  configs/<file named in BENCHMARK.json>   model sizes as run, source,
                                           reduced/assumed, departures
  traffic/<traffic>.json                   the training job's shape
  limits/<workload>.json                   correctness limits and the
                                           readings they were set from
  metrics/<per-layer metric>.py            ``read(ctx) -> float | None``
  references/<config "reference">.py       plain float32 reference and
                                           the model-FLOPs count

A later change adds a cell, a configuration or a metric by adding files
and entries; none of this code names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Imports one file by path (metric readers and references are
    found by name, not imported by the harness's own code)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names."""
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file's contents
    traffic: Dict[str, Any]         # traffic/<traffic>.json
    limits: Dict[str, Any]          # limits/<workload>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def reference(self):
        """The configuration's plain reference, ``references/<name>.py``."""
        return importlib.import_module("references." +
                                       self.config["reference"])


def _reports(metric: Dict[str, Any], workload: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or workload in cells


def load_cell(workload: str, benchmark: Optional[Dict[str, Any]] = None
              ) -> Cell:
    bench = benchmark or load_json(ROOT / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    w = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH_DIR / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"]
                    if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)])


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")).read
