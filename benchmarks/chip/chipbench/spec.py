"""What a cell is: its entry in BENCHMARK.json, its configuration file and
its traffic mix, each found by name."""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]


def load_benchmark(root: Path = ROOT) -> Dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise ValueError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def load_config(name: str) -> Dict:
    path = CHIP_DIR / "configs" / f"{name}.json"
    if not path.is_file():
        raise ValueError(f"unknown configuration {name!r}: no {path}")
    cfg = json.loads(path.read_text())
    if cfg.get("name") != name:
        raise ValueError(f"{path} names itself {cfg.get('name')!r}")
    return cfg


@dataclass
class Cell:
    name: str
    config: Dict
    mix: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]


def find_cell(workload: str, bench: Dict) -> Cell:
    from chipbench.traffic import load_mix

    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise ValueError(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise ValueError(f"workload {workload!r} names unknown configuration "
                         f"{w['config']!r}")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])]
    return Cell(name=workload, config=load_config(w["config"]),
                mix=load_mix(w["traffic"]), chips=int(w["chips"]),
                end_to_end=e2e, per_layer=layer)
