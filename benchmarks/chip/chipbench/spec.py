"""``BENCHMARK.json`` and the files it names, found by name.

* a configuration: ``configs/<config>.json`` (sizes as run, source,
  ``reduced``) and beside it ``configs/<config>.py``, its plain
  reference;
* a traffic mix: ``traffic/<traffic>.json``, parameters for the runner
  of its ``kind`` (``chipbench/<kind>.py``);
* a metric: ``metrics/<name>.py`` with ``read(run) -> float | None``;
* a cell's correctness limits: ``limits/<workload>.json``, with the
  readings they were set from, and ``smoke`` limits for a rehearsal;
* the chip's peaks: ``peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]   # benchmarks/chip
ROOT = HERE.parents[1]                       # the checkout


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict          # configs/<config>.json, smoke overrides applied
    traffic: Dict         # traffic/<traffic>.json, smoke overrides applied
    reference: ModuleType
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    smoke: bool

    def metric_reader(self, name: str):
        return load_module(HERE / "metrics" / f"{name}.py",
                           "metric_" + name.replace("-", "_"))


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, smoke: bool = False) -> Cell:
    bench = _json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _json(ROOT / cfg_entry["file"])
    traffic = _json(HERE / "traffic" / f"{w['traffic']}.json")
    if smoke:
        config["model"].update(config.get("smoke", {}))
        traffic.update(traffic.get("smoke", {}))
    limits = _json(HERE / "limits" / f"{workload}.json")
    if smoke:
        for name, limit in limits.get("smoke", {}).items():
            limits[name]["limit"] = limit
    ref_path = (ROOT / cfg_entry["file"]).with_suffix(".py")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic,
        reference=load_module(ref_path, "ref_" + w["config"].replace(
            "-", "_").replace(".", "_")),
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        smoke=smoke,
    )


def peaks(device_kind: str) -> Optional[Dict]:
    return _json(HERE / "peaks.json").get(device_kind)


def model_config(config: Dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    m = dict(config["model"])
    m["block_pattern"] = tuple(m["block_pattern"])
    return ModelConfig(**m)
