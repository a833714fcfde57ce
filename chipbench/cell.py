"""A benchmark cell, found by name: its entry in BENCHMARK.json, its
configuration file, its mix file and its own file under ``cells/``.

``cells/<cell>.json`` holds what belongs to the cell alone: the offered
rate and the limits of the correctness check. A cell at another rate is a
new cell with a file of its own.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    own: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def rate_per_s(self) -> float:
        return float(self.own["rate_per_s"])

    @property
    def limits(self) -> Dict[str, float]:
        return self.own["limits"]


def _read(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: Path = ROOT, base: Path = HERE) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files under
    ``base`` (the benchmark's directory)."""
    bench = _read(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _read(root / conf["file"])
    mix = _read(base / "mixes" / f"{entry['traffic']}.json")
    own = _read(base / "cells" / f"{name}.json")
    return Cell(name=name, chips=int(entry["chips"]), config=config, mix=mix,
                own=own,
                end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if applies(m, name)])
