"""Find the benchmark's data files by name.

Layout under the benchmark directory:

* ``configs/<name>.json``   — a model configuration as it is run;
* ``traffic/<name>.json``   — a traffic mix, read by ``traffic.generate``;
* ``workloads/<name>.json`` — a cell: configuration, traffic, engine knobs
  and the limits of its correctness check;
* ``metrics/<name>.py``     — a metric reader with ``read(ctx)``;
* ``references/<name>.py``  — a plain float32 reference forward.

A new configuration, mix, cell or metric is a new file; nothing here
lists them.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(ROOT))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _path(root: str, kind: str, name: str, ext: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(root, kind, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    return path


def load_json(root: str, kind: str, name: str) -> Dict[str, Any]:
    with open(_path(root, kind, name, ".json")) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    path = _path(root, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"onchip_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: str, name: str) -> Dict[str, Any]:
    """The cell's file with its configuration and traffic mix resolved."""
    cell = load_json(root, "workloads", name)
    cell["name"] = name
    cell["config_spec"] = load_json(root, "configs", cell["config"])
    cell["traffic_spec"] = load_json(root, "traffic", cell["traffic"])
    return cell


def load_benchmark(checkout: str = CHECKOUT) -> Dict[str, Any]:
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bench: Dict[str, Any], cell: str, trace: bool
                 ) -> List[Dict[str, Any]]:
    """The metric entries a run of ``cell`` reports: its end-to-end
    metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``.
    A metric without a ``workloads`` key belongs to every cell that
    reports the end-to-end metric it ``moves`` (per-layer) or to every
    cell (end-to-end)."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]
