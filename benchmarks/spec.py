"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A cell is one entry of ``workloads``: a configuration
(``configs/<name>.json``, the path is the entry's ``file``) under a
traffic mix (``traffic/<name>.json``). Whatever belongs to one
configuration, one mix, one generator or one per-layer metric sits in a
file of its own and is found here by name, so a later PR adds a cell by
adding files and entries and edits nothing that is there.
"""

from __future__ import annotations

import copy
import importlib
import json
import os
import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
_MODULE_NAME = re.compile(r"^[A-Za-z][A-Za-z0-9_]*$")


class SpecError(Exception):
    """The benchmark's own files do not fit together."""


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _merged(base: Dict[str, Any], over: Optional[Dict[str, Any]]):
    """``over`` laid on ``base``, nested dicts merged key by key."""
    out = copy.deepcopy(base)
    for key, val in (over or {}).items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = _merged(out[key], val)
        else:
            out[key] = copy.deepcopy(val)
    return out


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]


def load_cell(bench: Dict[str, Any], workload: str,
              tiny: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell ``workload`` with its configuration and mix read from
    their files. ``tiny`` (tests only) lays size overrides on both."""
    entry = next((w for w in bench["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SpecError(
            f"no workload {workload!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {workload!r} names the configuration "
                        f"{entry['config']!r}, which configs lacks")
    tiny = tiny or {}
    config = _merged(load_json(os.path.join(ROOT, cfg_entry["file"])),
                     tiny.get("config"))
    traffic = _merged(
        load_json(os.path.join(BENCH_DIR, "traffic",
                               entry["traffic"] + ".json")),
        tiny.get("traffic"))
    return Cell(name=workload, chips=int(entry["chips"]),
                config_name=entry["config"],
                traffic_name=entry["traffic"], config=config,
                traffic=traffic)


def metrics_for(bench: Dict[str, Any], section: str,
                workload: str) -> List[Dict[str, Any]]:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports:
    those without a ``workloads`` list, and those that list it. A
    per-layer metric is reported only where the metric it moves is."""
    def applies(entry):
        return "workloads" not in entry or workload in entry["workloads"]
    picked = [m for m in bench[section] if applies(m)]
    if section == "per_layer":
        e2e = {m["name"] for m in bench["end_to_end"] if applies(m)}
        picked = [m for m in picked if m["moves"] in e2e]
    return picked


def load_module(subdir: str, name: str):
    """``benchmarks/<subdir>/<name>.py``, found by name."""
    if not _MODULE_NAME.match(name):
        raise SpecError(f"{subdir} name {name!r} is not a module name")
    path = os.path.join(BENCH_DIR, subdir, name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"{path} does not exist")
    return importlib.import_module(f"benchmarks.{subdir}.{name}")
