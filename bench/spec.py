"""Finds a cell's pieces by name.

``BENCHMARK.json`` names the cells, configurations and metrics; each piece
is a file of its own under ``bench/``:

    bench/configs/<config>.json     a configuration (its ``file`` entry)
    bench/traffic/<traffic>.json    a traffic mix: its driver and parameters
    bench/drivers/<driver>.py       a driver of mixes: ``drive``, ``finish``
    bench/engines/<engine>.py       builds a configuration's engine
    bench/graphs/<generator>.py     a generator: ``generate(params, seed)``
    bench/apps/<app>.py             how the program is driven for an app
    bench/reference/<app>.py        the plain reference and its control
    bench/metrics/<metric>.py       a per-layer reader: ``read(run)``
    bench/work/<kernel>.py          a kernel's least work: ``work(view, cfg)``
    bench/peaks.json                peak rates, keyed by ``device_kind``

Adding a configuration, a mix, an engine or a metric adds files and
entries; no existing file changes.  Mixes that share a driver differ only
in data.  Every lookup takes the root of a checkout, so tests
can point it at a copy.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict        # the configuration file's contents
    traffic: dict       # the traffic mix's parameters
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=root)


def module(kind: str, name: str, root: str = ROOT):
    """Loads ``bench/<kind>/<name>.py`` by its path (a name may hold dots
    and dashes, which ``import`` does not take)."""
    path = os.path.join(root, "bench", kind, name + ".py")
    key = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    key += f"_{abs(hash(os.path.abspath(path)))}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The peak rates of ``device_kind``; a device not in the table is an
    error, not a default."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
