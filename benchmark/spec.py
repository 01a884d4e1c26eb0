"""Finds a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own; this module only joins them. A
later PR adds a file and an entry and edits nothing here.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]      # configs/<config>.json, as it is run
    traffic: Dict[str, Any]     # traffic/<traffic>.json: kind + parameters
    limits: Dict[str, float]    # workloads/<cell>.json: limit per number compared
    end_to_end: List[dict]      # the BENCHMARK.json entries this cell reports
    per_layer: List[dict]       # BENCHMARK.json entry + layer_metrics/<name>.json

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _lists(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Optional[str] = None) -> Cell:
    """The cell `name` as BENCHMARK.json under `root` declares it."""
    root = root or ROOT
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (has: {known})")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    bdir = os.path.join(root, bench["paths"][0])
    per_layer = []
    for m in bench["per_layer"]:
        if not _lists(m, name):
            continue
        reader = _read(os.path.join(bdir, "layer_metrics", m["name"] + ".json"))
        per_layer.append({**m, **reader})
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        config=_read(os.path.join(root, cfg_entry["file"])),
        traffic=_read(os.path.join(bdir, "traffic", entry["traffic"] + ".json")),
        limits=_read(os.path.join(bdir, "workloads", name + ".json"))["limits"],
        end_to_end=[m for m in bench["end_to_end"] if _lists(m, name)],
        per_layer=per_layer,
    )


def load_peaks(device_kind: str) -> dict:
    """Published peaks of the device; a device not in the table is an error."""
    table = _read(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(has: {', '.join(table['devices'])}); add it with its source"
        )
    return table["devices"][device_kind]


def all_layer_metric_files() -> List[str]:
    return sorted(glob.glob(os.path.join(BENCH_DIR, "layer_metrics", "*.json")))
