"""BENCHMARK.json against the contract's limits and the benchmark's data files."""
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cells  # noqa: E402
from benchmark.tools import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_is_what_the_data_files_say():
    assert manifest.build(load()) == load()


def test_manifest_keeps_the_contracts_limits():
    m = load()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert 2 <= len(m["workloads"]) <= 24 and 1 <= len(m["per_layer"]) <= 128
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in m["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for e in m["workloads"]:
        assert set(e) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(e["traffic"]) and 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for e in m["configs"]:
        assert set(e) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(e["source"]) <= 200 and len(e["reduced"]) <= 16
        assert os.path.exists(os.path.join(REPO, e["file"]))
        assert any(e["file"].startswith(p + "/") for p in m["paths"])
        assert not any(re.search(r"(_dim|_rank|hidden|intermediate|head)", k) for k in e["reduced"])
    assert len({e["source"] for e in m["configs"]}) == len(m["configs"])
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace") and 0.01 <= e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert e["source"] in SOURCES and 1 <= len(e["layer"]) <= 200
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    assert any(e["name"] == "setup_s" and "workloads" not in e for e in m["end_to_end"])


def test_every_cell_reports_what_the_contract_asks():
    m = load()
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for w in m["workloads"]:
        here = lambda e: w["name"] in e.get("workloads", [w["name"]])
        mine = [n for n, e in e2e.items() if here(e)]
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        layer = [e for e in m["per_layer"] if here(e)]
        assert layer, w["name"]
        # a per-layer metric is reported only where the metric it moves is
        assert all(e["moves"] in mine for e in layer), w["name"]


def check_seconds(n_cells, run_seconds, warm, cold):
    """A full check of ``n_cells``: 2 + 14 runs a cell, every run paying its
    warm set-up and the window, two runs a cell paying the cold set-up, 1200 s spare."""
    return (2 + 14 * n_cells) * (run_seconds + warm) + n_cells * 2 * (cold - warm) + 1200


def test_a_full_check_fits_its_limit():
    m = load()
    # the contract's own sum for 24 cells, which decides the longest run_seconds
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    # the same with the set-up measured on the chip in place of the sum's 60 s and 90 s
    measured = [cells.load("workloads", w["name"])["measured"] for w in m["workloads"]]
    warm = sum(c["setup_warm_s"] for c in measured) / len(measured)
    cold = sum(c["setup_cold_s"] for c in measured) / len(measured)
    assert check_seconds(len(measured), m["run_seconds"], warm, cold) <= 43200
    # cells like today's (their mean) fit 23 times, not 24: PERF.md section 7 lists
    # what would shorten the program's set-up
    assert max(n for n in range(1, 25) if check_seconds(n, m["run_seconds"], warm, cold) <= 43200) >= 23
    # no run nears the limit of one run: 360 s warm, 1200 s cold
    assert all(c["setup_warm_s"] + m["run_seconds"] < 180 and c["setup_cold_s"] < 600 for c in measured)


def test_a_later_pr_adds_a_cell_and_a_metric_as_files_of_their_own(tmp_path, monkeypatch):
    """New files and new entries, no edit to a file that is there: a new cell
    lists the metrics it reports, a new metric lists the cells that were
    there before it, and BENCHMARK.json's old entries keep their place."""
    import shutil

    here = tmp_path / "benchmark"
    for kind in ("configs", "traffic", "workloads", "end_to_end", "layer_metrics"):
        shutil.copytree(os.path.join(cells.HERE, kind), here / kind)
    before = {p: p.read_bytes() for p in here.rglob("*.json")}
    cell = cells.load("workloads", "sl_b6t64")
    traffic = cells.load("traffic", "sl_pool4_b6t64")
    traffic.update(name="sl_pool8_b6t64", params=dict(traffic["params"], pool=8))
    metric = cells.load("layer_metrics", "data_wait_ms")
    metric.update(name="data_wait_p95_ms", workloads=["sl_dp4_b24t64"],
                  params=dict(metric["params"], reduce="p95"))
    cell.update(name="sl_pool8", traffic="sl_pool8_b6t64", why="as sl_b6t64, a pool of 8",
                per_layer=["step_busy_ms", "data_wait_ms", "data_wait_p95_ms"])
    for kind, new in (("traffic", traffic), ("layer_metrics", metric), ("workloads", cell)):
        (here / kind / f"{new['name']}.json").write_text(json.dumps(new))
    monkeypatch.setattr(cells, "HERE", str(here))

    old, new = load(), manifest.build(load())
    assert {p: p.read_bytes() for p in before} == before  # nothing that was there changed
    assert [w["name"] for w in new["workloads"]] == [w["name"] for w in old["workloads"]] + ["sl_pool8"]
    assert new["configs"] == old["configs"] and new["end_to_end"] == old["end_to_end"]
    assert [e["name"] for e in new["per_layer"]] == [e["name"] for e in old["per_layer"]] + ["data_wait_p95_ms"]
    where = {e["name"]: e["workloads"] for e in new["per_layer"]}
    assert where["data_wait_p95_ms"] == ["sl_dp4_b24t64", "sl_pool8"]
    assert where["step_busy_ms"] == [w["name"] for w in old["workloads"]] + ["sl_pool8"]
    assert "sl_pool8" not in where["mfu_pct"]
    for e_old, e_new in zip(old["per_layer"], new["per_layer"]):  # an old entry only gains the cell
        assert {k: v for k, v in e_new.items() if k != "workloads"} == \
            {k: v for k, v in e_old.items() if k != "workloads"}
    assert [m["name"] for m in cells.layer_metrics(cells.load_cell("sl_pool8"))] == cell["per_layer"]
    assert "data_wait_p95_ms" in [m["name"] for m in cells.layer_metrics(cells.load_cell("sl_dp4_b24t64"))]


def test_the_harness_branches_on_no_cells_name():
    """Cells, configurations, traffic mixes and metrics are data: no Python
    file of the benchmark may mention one by name."""
    data_names = set()
    for kind in ("workloads", "configs", "traffic", "layer_metrics"):
        data_names.update(cells.names(kind))
    offenders = []
    for root, _, files in os.walk(os.path.join(REPO, "benchmark")):
        for f in files:
            if not f.endswith(".py") or os.path.basename(root) == "tools":
                continue
            with open(os.path.join(root, f)) as fh:
                code = "\n".join(ln for ln in fh.read().split("\n")
                                 if not ln.lstrip().startswith("#"))
            code = re.sub(r'""".*?"""', "", code, flags=re.S)
            for n in data_names:
                if re.search(rf"[\"']{re.escape(n)}[\"']", code):
                    offenders.append((f, n))
    assert not offenders, offenders


def test_data_files_resolve():
    for name in cells.names("workloads"):
        cell = cells.load_cell(name)
        cells.module("drivers", cell["driver"])
        cells.module("gen", cell["traffic"]["generator"])
        cells.module("references", cell["config"]["reference"]["first_step"])
        listed = cells.layer_metrics(cell)
        assert listed and all(m["moves"] in cell["end_to_end"] for m in listed)
    for name in cells.names("layer_metrics"):
        cells.module("readers", cells.load("layer_metrics", name)["reader"])
