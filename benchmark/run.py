"""One process, one cell, one contract line.

  python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and ``breakdown`` when traced): with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics. Earlier lines are for people. Without a TPU that holds
the cell's chips the run exits non-zero and prints no result; ``--rehearse``
runs the same code at the configuration's tiny preset on the CPU and prints
the line with counts only: no rate, no time.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

faulthandler.enable()  # a fatal signal leaves a traceback, not silence
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # also when started as a file
    sys.path.insert(0, ROOT)

from benchmark import cells, check  # noqa: E402
from benchmark.device import NoAccelerator  # noqa: E402

OUT = os.path.join(ROOT, "benchmark_out")  # git-ignored; traces, losses, logs


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def per_layer(cell: dict, result: dict) -> dict:
    """The cell's per-layer metrics, each from its reader; a reader that
    finds nothing to read gives None and the metric is left out."""
    out = {}
    for m in cells.layer_metrics(cell):
        value = cells.module("readers", m["reader"]).read(result, **m.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(cell: dict, result: dict) -> dict:
    out = {}
    for name in cell["end_to_end"]:
        value = result["end_to_end"].get(name)
        if value is not None:
            out[name] = {"value": value, "unit": cells.load("end_to_end", name)["unit"]}
    return out


def contract_line(cell: dict, result: dict, trace: bool, rehearse: bool) -> dict:
    line = {
        "correct": not check.failed_checks(result["checks"]) and result["failed"] == 0,
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": {} if rehearse else (per_layer if trace else end_to_end)(cell, result),
        "device": result["device"],
    }
    if rehearse:
        # which metrics a measured run would carry, without a value: a number
        # from a CPU run is never written under a device metric's name
        line["rehearsed"] = sorted((per_layer if trace else end_to_end)(cell, result))
    elif trace and result.get("events"):
        from benchmark import trace_reduce

        b = trace_reduce.busy(result["events"])
        line["device"].update(busy_s=b["busy_s"], window_s=b["window_s"])
        line["breakdown"] = trace_reduce.breakdown(result["events"])
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny preset on the CPU; the line carries counts only")
    args = p.parse_args(argv)

    cell = cells.load_cell(args.workload)
    seconds = args.seconds if args.seconds is not None else manifest()["run_seconds"]
    out_dir = os.path.join(OUT, cell["name"] + ("_rehearsal" if args.rehearse else ""))
    os.makedirs(out_dir, exist_ok=True)
    try:
        result = cells.module("drivers", cell["driver"]).run(
            cell, seed=args.seed, seconds=seconds, trace=bool(args.trace),
            rehearse=args.rehearse, out_dir=out_dir, t0=T0)
    except NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    bad = check.failed_checks(result["checks"])
    if bad:
        print(f"benchmark: checks that failed: {bad}", flush=True)
    print(json.dumps(contract_line(cell, result, bool(args.trace), args.rehearse)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
