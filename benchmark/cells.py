"""The benchmark's data files, found by name.

One file per configuration (``configs/``), traffic mix (``traffic/``), cell
(``workloads/``: every file there is a cell), end-to-end metric
(``end_to_end/``) and per-layer metric (``layer_metrics/``); drivers,
generators and readers are modules looked up by the name a data file gives.
Nothing here, or in ``run.py``, branches on a cell's name.
"""
from __future__ import annotations

import importlib
import json
import os
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        data = json.load(f)
    if data.get("name", name) != name:
        raise ValueError(f"{path} names itself {data['name']!r}")
    return data


def names(kind: str) -> List[str]:
    return sorted(f[:-5] for f in os.listdir(os.path.join(HERE, kind)) if f.endswith(".json"))


def load_cell(name: str) -> dict:
    """A cell with its configuration and traffic files resolved in place."""
    cell = load("workloads", name)
    cell["config"] = load("configs", cell["config"])
    cell["traffic"] = load("traffic", cell["traffic"])
    return cell


def layer_metrics(cell: dict) -> List[dict]:
    """The per-layer metrics a cell reports: those its own file lists
    (``per_layer``: the metrics that were there when the cell was added),
    then those whose file lists the cell (``workloads``: the cells that were
    there when the metric was added). Either side is added as a new file."""
    listed = list(cell.get("per_layer", []))
    later = [n for n in names("layer_metrics")
             if n not in listed and cell["name"] in load("layer_metrics", n).get("workloads", [])]
    return [load("layer_metrics", n) for n in listed + later]


def module(package: str, name: str):
    """``benchmark/<package>/<name>.py``, e.g. a driver, generator or reader."""
    return importlib.import_module(f"benchmark.{package}.{name}")


def program_config(config: dict):
    """The program's config file a configuration names, after checking that
    it still says what the benchmark's file says it runs (``as_run``)."""
    from distar_tpu.utils import read_config

    program = read_config(os.path.join(ROOT, config["program_file"]))
    for section, want in config["as_run"].items():
        have = {k: program.get(section, {}).get(k) for k in want}
        if have != want:
            raise ValueError(f"{config['program_file']} [{section}] is {have}, the benchmark's "
                             f"configuration file says it runs {want}")
    return program
