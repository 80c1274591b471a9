"""The benchmark's own description, read from ``BENCHMARK.json``.

A cell is found by its name; its configuration, traffic mix, generator,
core kinds and per-layer metric readers are files named after the entries
there: the configuration's ``file`` (whose cores each name a ``kind``,
``bench/cores/<kind>.py``), ``bench/traffic/<traffic>.json`` (whose
``kind`` names ``bench/traffic/<kind>.py``) and
``bench/metrics/<metric>.py``.  Adding one is adding files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import sys
from types import ModuleType
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class Refused(RuntimeError):
    """A run the harness will not make: no chip it knows, too few chips,
    or a configuration the cell cannot run as stated."""


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: Dict
    traffic: str
    mix: Dict
    chips: int
    generator: ModuleType
    kinds: Dict[str, ModuleType]     # core name -> bench/cores/<kind>.py
    end_to_end: List[Dict]
    per_layer: List[Dict]


def read_json(path: pathlib.Path) -> Dict:
    return json.loads(pathlib.Path(path).read_text())


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    return read_json(root / "BENCHMARK.json")


def load_module(path: pathlib.Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _for_cell(metrics: List[Dict], cell: str, e2e: List[str]) -> List[Dict]:
    """Metrics a cell reports: those listing it, or, with no list, every
    cell that reports the end-to-end metric they move."""
    out = []
    for m in metrics:
        cells = m.get("workloads")
        if cells is None and "moves" in m:
            ok = m["moves"] in e2e
        else:
            ok = cells is None or cell in cells
        if ok:
            out.append(m)
    return out


def cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = read_json(root / conf["file"])
    if int(config["chips"]) != int(entry["chips"]):
        raise Refused(f"configuration {conf['name']} runs on "
                      f"{config['chips']} chips, cell {name} asks for "
                      f"{entry['chips']}")
    if int(config["chips"]) > 1 and not config.get("mesh"):
        raise Refused(f"configuration {conf['name']} spans "
                      f"{config['chips']} chips with no mesh")
    mix = read_json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    gen = load_module(root / "bench" / "traffic" / f"{mix['kind']}.py",
                      f"bench_traffic_{mix['kind']}")
    kinds = {c["name"]: load_module(root / "bench" / "cores" /
                                    f"{c['kind']}.py", f"bench_core_{c['kind']}")
             for c in config["cores"]}
    e2e = _for_cell(bench["end_to_end"], name, [])
    names = [m["name"] for m in e2e]
    return Cell(name=name, config_name=entry["config"], config=config,
                traffic=entry["traffic"], mix=mix, chips=int(entry["chips"]),
                generator=gen, kinds=kinds, end_to_end=e2e,
                per_layer=_for_cell(bench["per_layer"], name, names))


def reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read(obs)`` function of one per-layer metric."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    return load_module(path, "bench_metric_" + metric.replace(".", "_")).read
