"""Write (or check) BENCHMARK.json from the benchmark's data files.

The manifest's lists are what the data files say, in one place for the
driver: every file under ``workloads/`` is a cell; configurations, end-to-end
and per-layer metrics are those a cell names (a per-layer metric also where
its own file names the cell, see ``cells.layer_metrics``). Entries
BENCHMARK.json already has keep their place and new ones follow, so a later
PR's files only append. ``command``, ``paths`` and ``run_seconds`` are kept
as BENCHMARK.json has them: a later PR may not change those, nor a bound.

  python -m benchmark.tools.manifest          # rewrite BENCHMARK.json
  python -m benchmark.tools.manifest --check  # exit 1 if it would change
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import cells  # noqa: E402

PATH = os.path.join(ROOT, "BENCHMARK.json")


def _ordered(known: list, found: list) -> list:
    return [n for n in known if n in found] + sorted(n for n in found if n not in known)


def build(current: dict) -> dict:
    was = {group: [e["name"] for e in current[group]]
           for group in ("configs", "workloads", "end_to_end", "per_layer")}
    loaded = [cells.load("workloads", n)
              for n in _ordered(was["workloads"], cells.names("workloads"))]
    configs = []
    for name in _ordered(was["configs"], list({c["config"] for c in loaded})):
        cfg = cells.load("configs", name)
        configs.append({"name": name, "source": cfg["source"][:200],
                        "file": f"benchmark/configs/{name}.json",
                        "reduced": cfg["reduced"], "why": cfg["why"]})
    reports = {c["name"]: [m["name"] for m in cells.layer_metrics(c)] for c in loaded}
    per_layer = []
    for name in _ordered(was["per_layer"], list({n for ns in reports.values() for n in ns})):
        m = cells.load("layer_metrics", name)
        per_layer.append({"name": name, "unit": m["unit"], "better": m["better"],
                          "source": m["source"], "layer": m["layer"], "moves": m["moves"],
                          "workloads": [c for c, ns in reports.items() if name in ns]})
    end_to_end = []
    for name in _ordered(was["end_to_end"], list({n for c in loaded for n in c["end_to_end"]})):
        m = cells.load("end_to_end", name)
        where = [c["name"] for c in loaded if name in c["end_to_end"]]
        entry = {"name": name, "unit": m["unit"], "better": m["better"],
                 "bound": m["bound"], "source": m["source"]}
        if len(where) < len(loaded):
            entry["workloads"] = where
        end_to_end.append(entry)
    return {
        "command": current["command"], "paths": current["paths"],
        "run_seconds": current["run_seconds"], "configs": configs,
        "workloads": [{"name": c["name"], "config": c["config"], "traffic": c["traffic"],
                       "chips": c["chips"], "why": c["why"]} for c in loaded],
        "end_to_end": end_to_end, "per_layer": per_layer,
    }


def main() -> int:
    with open(PATH) as f:
        current = json.load(f)
    new = build(current)
    if "--check" in sys.argv:
        if new != current:
            print("BENCHMARK.json does not match the data files under benchmark/")
            return 1
        return 0
    with open(PATH, "w") as f:
        json.dump(new, f, indent=1)
        f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
