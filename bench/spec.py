"""Find a cell's parts by name: ``BENCHMARK.json`` names them, files hold them.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``).  Its per-layer metrics are
the ``per_layer`` entries that list it under ``workloads`` (or, without
that key, every cell that reports the metric the entry ``moves``); each is
read by ``bench/metrics/<name>.py``.  Adding a configuration, a mix or a
metric is adding files and entries: nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_part_{path.parent.name}_{path.stem}".replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file.name}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return make_cell(name, ROOT / conf["file"], w["traffic"], int(w["chips"]),
                     bench)


def make_cell(name: str, config_file: Path, traffic: str, chips: int,
              bench: Dict) -> Cell:
    """A cell from its files, with the metrics ``bench`` gives a cell of
    this name."""
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m
            else m["moves"] in e2e_names)
    ]
    return Cell(
        name=name,
        chips=chips,
        config=load_json(config_file),
        traffic=load_json(BENCH / "traffic" / f"{traffic}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py")


def reference(kind: str) -> ModuleType:
    return importlib.import_module(f"bench.refs.{kind}")


def flops(kind: str) -> ModuleType:
    return importlib.import_module(f"bench.flops.{kind}")


def peaks(device_kind: str) -> Dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table["devices"][device_kind]
