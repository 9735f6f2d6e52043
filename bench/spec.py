"""Loading the benchmark's data files by name.

``BENCHMARK.json`` at the checkout's root names the cells; each cell names a
configuration (``bench/configs/<config>.json``) and its traffic mix
(``bench/workloads/<cell>.json``); each per-layer metric has a reader
(``bench/metrics/<metric>.py``) with a ``read(run)`` function.  The files
name the code they need the same way: a configuration's ``path`` names its
system module (``bench/systems/<path>.py``), a traffic mix's scenes and
trajectory generator name ``bench/scenes/<kind>.py`` and
``bench/trajectories/<generator>.py``.  Adding a configuration, a traffic
mix, a scene, a camera path, a system or a metric is adding files and entries:
nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    chips: int
    config: dict       # bench/configs/<config>.json
    traffic: dict      # bench/workloads/<cell>.json
    end_to_end: list   # BENCHMARK.json entries this cell reports
    per_layer: list


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    return Cell(
        name=name, config_name=w["config"], chips=w["chips"],
        config=_json(os.path.join(root, conf["file"])),
        traffic=_json(os.path.join(HERE, "workloads", f"{name}.json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded once per process."""
    key = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        have = sorted(f[:-3] for f in os.listdir(os.path.join(HERE, kind))
                      if f.endswith(".py"))
        raise KeyError(f"no bench/{kind}/{name}.py (have: {have})")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod          # its classes look themselves up here
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def load_reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return load_module("metrics", metric).read


def load_peaks() -> dict:
    return _json(os.path.join(HERE, "peaks.json"))


def peaks_for(device_kind: str, peaks: dict | None = None) -> dict:
    """The row of the peaks table for ``device_kind``; a kind missing from
    the table is an error, never a default."""
    table = (peaks or load_peaks())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no row in "
                       f"bench/peaks.json (rows: {sorted(table)})")
    return table[device_kind]
