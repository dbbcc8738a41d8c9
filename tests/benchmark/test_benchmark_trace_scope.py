"""The names in a trace: the decoder of the ``.xplane.pb`` (``trace_meta``), the
per-scope self time (``readers/trace_scope``) and the idle by run-loop span
(``readers/trace_idle_in_span``), on the traces recorded on the chip under
``tests/benchmark/data`` and on hand-made timelines."""
import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cells, run, trace_meta, trace_reduce  # noqa: E402
from benchmark.readers import trace_idle_in_span, trace_scope  # noqa: E402
from benchmark.trace_meta import Op, Span  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "tpu_v5e_1chip.xplane.pb")           # PR 22: 4 runs of a matmul chain
STEPS = os.path.join(DATA, "tpu_v5e_sl_tiny_steps.xplane.pb")   # PR 23: 3 steps of the tiny SL learner
PLANE = "/device:TPU:0"


# ------------------------------------------------------------ the decoder
def test_decoder_reads_scope_and_category_of_a_device_op():
    meta = trace_meta.parse(SMALL)
    ops = meta.ops[PLANE]
    events = trace_reduce.load(SMALL)
    assert len(ops) == len(trace_reduce.op_intervals(events, PLANE)) == 24
    fusion6 = [o for o in ops if trace_reduce.short_name(o.name).startswith("fusion.6 ")]
    assert len(fusion6) == 4
    assert {o.scope for o in fusion6} == {"jit(step)/dot_general:"}
    assert {o.category for o in fusion6} == {"convolution fusion"}
    assert {o.category for o in ops} == {"convolution fusion", "copy-start", "copy-done"}
    assert meta.spans == []  # that trace has the benchmark's bench: spans only


def test_decoder_agrees_with_profile_data_on_names_and_times():
    for path in (SMALL, STEPS):
        mine = trace_meta.parse(path).ops[PLANE]
        theirs = sorted(trace_reduce.op_intervals(trace_reduce.load(path), PLANE), key=lambda o: o[1])
        assert len(mine) == len(theirs)
        for o, (name, a, b) in zip(mine, theirs):  # ProfileData cuts to whole ns
            assert o.name == name and abs(o.start - a) < 2 and abs(o.end - b) < 2


def test_decoder_agrees_with_tensorflows_parser():
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    with open(STEPS, "rb") as f:
        space.ParseFromString(f.read())
    plane = next(p for p in space.planes if p.name == PLANE)
    stat = {k: v.name for k, v in plane.stat_metadata.items()}
    want = {}
    for meta in plane.event_metadata.values():
        want[meta.name] = {stat[s.metadata_id]: s.str_value for s in meta.stats}
    got = {o.name: {"tf_op": o.scope, "hlo_category": o.category} for o in trace_meta.parse(STEPS).ops[PLANE]}
    assert got and all({k: v for k, v in want[n].items()} == {k: v for k, v in s.items() if v}
                       for n, s in got.items())


def test_decoder_reads_the_programs_spans_by_thread():
    spans = trace_meta.parse(STEPS).spans
    loop = {s.thread for s in spans if s.name.startswith("loop/")}
    feed = {s.thread for s in spans if s.name.startswith("feed/")}
    assert len(loop) == 1 and len(feed) == 1 and loop != feed  # both lines are called "python"
    assert {s.name for s in spans} == {
        "loop/data_wait", "loop/pre_step", "loop/device_step", "loop/prepare", "loop/dispatch",
        "loop/fetch", "loop/post_step", "loop/host_callback", "loop/tick",
        "feed/pull", "feed/cap", "feed/put", "feed/put_wait"}
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    for parent in by_name["loop/device_step"]:  # the children lie inside their parent
        inside = [s for n in ("loop/prepare", "loop/dispatch", "loop/fetch") for s in by_name[n]
                  if parent.start <= s.start and s.end <= parent.end]
        assert len(inside) == 3


def test_parse_is_memoised_per_path():
    assert trace_meta.parse(STEPS) is trace_meta.parse(os.path.join(DATA, ".", os.path.basename(STEPS)))


# ----------------------------------------------------- scope and pass names
@pytest.mark.parametrize("path,scope,pas", [
    ("jit(train_step)/jvp(Model.sl_forward)/encoder/scatter_connection/FCBlock_0/dot_general",
     "scatter_connection", "forward"),
    ("jit(train_step)/transpose(jvp(Model.sl_forward))/policy.train_forward/selected_units_head/while/body/mul",
     "selected_units_head", "backward"),
    ("jit(train_step)/transpose(jvp(Model.sl_forward))/encoder/jvp(Model.sl_forward)/encoder/checkpoint/"
     "rematted_computation/spatial_encoder/ResBlock_0/conv_general_dilated", "spatial_encoder", "recompute"),
    ("jit(train_step)/transpose(jvp(Model.sl_forward))/encoder/jvp(Model.sl_forward)/encoder/checkpoint/"
     "spatial_encoder/ResBlock_0/conv_general_dilated", "spatial_encoder", "backward"),
    ("jit(train_step)/jvp(loss)/vtrace/while/body/add", "loss", "forward"),
    ("jit(train_step)/transpose(jvp(loss))/jit(log_softmax)/div", "loss", "backward"),
    ("jit(train_step)/optimizer/sqrt", "optimizer", "forward"),
    ("jit(train_step)/diagnostics/dynamics_tree/reduce_sum", "diagnostics", "forward"),
    ("jit(train_step)/jvp(Model.rl_forward)/value/value_winloss/FCBlock_0/dot_general", "value", "forward"),
    ("selected_units_head.<lambda>/selected_units_head._lstm/lstm0/lstm0.step_from_proj/add",
     "selected_units_head", "forward"),
    # the first vocabulary element wins: the entity encoder's embeddings inside the head
    ("jit(train_step)/jvp(Model.sl_forward)/policy.train_forward/target_unit_head/entity_encoder", "target_unit_head",
     "forward"),
    ("jit(train_step)/jvp(Model.sl_forward)/encoder/concatenate", "unnamed", "forward"),
    ("", "unnamed", "forward"),
])
def test_scope_and_pass_of_a_path(path, scope, pas):
    assert (trace_scope.scope_of(path), trace_scope.pass_of(path)) == (scope, pas)


def test_readers_vocabulary_is_the_programs():
    from distar_tpu import obs

    assert trace_scope.VOCABULARY is obs.STEP_SCOPES and set(trace_scope.EVERY_STEP_HAS) <= set(obs.STEP_SCOPES)


# ------------------------------------------------------------- self time
def op(name, start, end, scope):
    return Op(name, float(start), float(end), scope, "")


def test_self_time_gives_a_container_only_what_its_children_leave():
    core, head = "jit(s)/jvp(M)/core_lstm/while", "jit(s)/transpose(jvp(M))/location_head/x"
    ops = [op("while", 0, 100, core),                 # container: 100 long
           op("body.1", 10, 40, core + "/body/dot"),  # its children: 30 + 40
           op("body.2", 50, 90, core + "/body/dot"),
           op("conv", 100, 160, head),                # starts where the while ends
           op("tail", 150, 180, ""),                  # overlaps the conv without being inside it
           op("late", 300, 310, "jit(s)/optimizer/add")]
    got = trace_scope.self_times(ops)
    assert got == {("core_lstm", "forward"): 100.0, ("location_head", "backward"): 50.0,
                   ("unnamed", "forward"): 30.0, ("optimizer", "forward"): 10.0}
    # the scopes partition the union of the intervals: 0-180 and 300-310
    assert sum(got.values()) == trace_reduce.total(trace_reduce.union((o.start, o.end) for o in ops))


def test_self_time_of_a_child_in_another_scope_is_its_own():
    ops = [op("while", 0, 100, "jit(s)/jvp(M)/selected_units_head/while"),
           op("gather", 20, 50, "jit(s)/jvp(M)/selected_units_head/while/body/entity_encoder/gather"),
           op("copy", 60, 70, "")]
    assert trace_scope.self_times(ops) == {("selected_units_head", "forward"): 90.0,
                                           ("unnamed", "forward"): 10.0}


def test_per_step_clips_operations_to_the_runs_of_the_step_program():
    ops = [op("a", 0, 10, "jit(s)/optimizer/x"), op("b", 95, 110, "jit(s)/jvp(loss)/y"),
           op("c", 205, 230, "jit(s)/optimizer/x")]
    steps = trace_scope.per_step(ops, [(0.0, 100.0), (200.0, 300.0)])
    assert steps == [{("optimizer", "forward"): 10.0, ("loss", "forward"): 5.0},
                     {("optimizer", "forward"): 25.0}]


# -------------------------------------------------- idle by run-loop span
def test_idle_goes_to_the_innermost_span_open_at_each_instant():
    spans = [Span("loop/device_step", 0, 100, "t"), Span("loop/dispatch", 5, 30, "t"),
             Span("loop/fetch", 30, 95, "t"), Span("loop/data_wait", 104, 140, "t"),
             Span("feed/put", 0, 200, "f")]  # another role never explains a gap
    loop = [s for s in spans if s.name.startswith(trace_idle_in_span.ROLE)]
    assert trace_idle_in_span.innermost(loop, 10) == "dispatch"
    assert trace_idle_in_span.innermost(loop, 97) == "device_step"
    assert trace_idle_in_span.innermost(loop, 102) == "unspanned"
    busy = [(0, 8), (20, 90), (110, 120), (150, 160)]
    # 8-20 lies in dispatch; 90-110 straddles fetch (5), device_step outside its children (5),
    # nothing (4) and data_wait (6); of 120-150, 20 are in data_wait and 10 in nothing
    assert trace_idle_in_span.idle_by_phase(busy, loop) == {
        "dispatch": 12, "fetch": 5, "device_step": 5, "unspanned": 14, "data_wait": 26}


@pytest.mark.parametrize("steps", [2, 3, 5])
def test_idle_is_per_whole_cycle_whatever_the_number_of_traced_steps(steps, monkeypatch):
    """Each step is busy 0-40 and 45-90 of its 100 and idles 5 inside (under
    fetch) and 10 behind it (under data_wait): 5 and 10 a cycle, not 10 gaps
    over 11 steps' worth."""
    ops, spans, runs = [], [], []
    for k in range(steps):
        t = 100.0 * k
        ops += [op("a", t, t + 40, "jit(s)/jvp(loss)/x"), op("b", t + 45, t + 90, "jit(s)/optimizer/y")]
        spans += [Span("loop/fetch", t + 1, t + 91, "t"), Span("loop/data_wait", t + 91, t + 100, "t")]
        runs.append((t, t + 90))
    by_phase = trace_idle_in_span.whole_cycles(ops, runs, spans)
    assert by_phase == {"fetch": 6.0 * (steps - 1), "data_wait": 9.0 * (steps - 1)}
    meta = trace_meta.Meta({PLANE: ops}, spans)
    monkeypatch.setattr(trace_meta, "find", lambda result: meta)
    monkeypatch.setattr(trace_meta, "step_runs", lambda events: (PLANE, runs))
    monkeypatch.setattr(trace_idle_in_span, "_idle", {})
    assert trace_idle_in_span.read({"events": []}, phases=["fetch"], scale=1.0) == 6.0
    assert trace_idle_in_span.read({"events": []}, phases=["fetch", "data_wait"], scale=1.0) == 15.0


def test_one_traced_step_has_no_whole_cycle(monkeypatch):
    meta = trace_meta.Meta({PLANE: [op("a", 0, 40, "jit(s)/optimizer/y")]}, [Span("loop/fetch", 0, 50, "t")])
    monkeypatch.setattr(trace_meta, "find", lambda result: meta)
    monkeypatch.setattr(trace_idle_in_span, "_idle", {})
    for runs in ([(0.0, 40.0)], []):
        monkeypatch.setattr(trace_meta, "step_runs", lambda events, runs=runs: (PLANE, runs))
        trace_idle_in_span._idle.clear()
        assert trace_idle_in_span.read({"events": []}, phases=["fetch"]) is None


# ------------------------------------------- the trace recorded on the chip
@pytest.fixture
def traced_result(tmp_path, monkeypatch):
    """A driver's result for a run whose trace is the recorded one: the file
    under ``<OUT>/<cell>/trace/plugins/profile/<time>/``, written after T0."""
    out = tmp_path / "benchmark_out"
    where = out / "sl_b6t64" / "trace" / "plugins" / "profile" / "2026_09_27"
    where.mkdir(parents=True)
    older = out / "another" / "trace" / "plugins" / "profile" / "2026_09_26"
    older.mkdir(parents=True)
    (older / "vm.xplane.pb").write_bytes(open(SMALL, "rb").read())
    os.utime(older / "vm.xplane.pb", (time.time() - 3600,) * 2)  # an earlier process wrote it
    (where / "vm.xplane.pb").write_bytes(open(STEPS, "rb").read())
    monkeypatch.setattr(run, "OUT", str(out))
    monkeypatch.setattr(run, "T0", time.perf_counter() - 60.0)
    return {"events": trace_reduce.load(STEPS), "values": {}}


def metric(name, result):
    m = cells.load("layer_metrics", name)
    return cells.module("readers", m["reader"]).read(result, **m["params"])


def test_recorded_steps_read_as_they_were_read_by_hand(traced_result):
    with open(os.path.join(DATA, "tpu_v5e_sl_tiny_steps.expected.json")) as f:
        want = json.load(f)
    close = lambda got, ms: got == pytest.approx(ms, rel=2e-3, abs=2e-4)
    scopes = {n: cells.load("layer_metrics", n)["params"]["scopes"]
              for n in cells.names("layer_metrics") if n.startswith("scope_")}
    assert len(scopes) == 13 and sorted(p for parts in scopes.values() for p in parts) == \
        sorted(trace_scope.VOCABULARY + (trace_scope.UNNAMED,))  # each scope in one metric
    got = {name: metric(name, traced_result) for name in scopes}
    for name, parts in scopes.items():
        assert close(got[name], sum(want["scopes_ms"][p] for p in parts)), name
    # the scopes partition step_busy_ms
    busy = metric("step_busy_ms", traced_result)
    assert close(busy, want["busy_ms"]) and sum(got.values()) == pytest.approx(busy, rel=2e-3)
    passes = {p: metric(f"step_{p}_ms", traced_result) for p in ("forward", "backward", "recompute")}
    for p, ms in want["passes_ms"].items():
        assert close(passes[p], ms), p
    # the passes leave out what has none: loss, optimizer, diagnostics and the unnamed
    assert sum(passes.values()) + got["scope_loss_ms"] + got["scope_optimizer_ms"] \
        + got["scope_diagnostics_ms"] + got["scope_unnamed_ms"] == pytest.approx(busy, rel=2e-3)
    idle = {n: metric(n, traced_result) for n in cells.names("layer_metrics") if n.startswith("idle_")}
    by_hand = want["idle_ms_per_cycle"]
    assert close(idle["idle_in_data_wait_ms"], by_hand["data_wait"])
    assert close(idle["idle_in_dispatch_ms"], by_hand["dispatch"])
    assert close(idle["idle_in_fetch_ms"], by_hand["fetch"])
    assert close(idle["idle_in_host_tail_ms"], sum(by_hand[p] for p in (
        "pre_step", "prepare", "device_step", "post_step", "host_callback", "tick")))
    assert close(idle["idle_unspanned_ms"], by_hand["unspanned"]) and idle["idle_unspanned_ms"] < 0.1
    # they partition the idle of a whole cycle, a step and the gap behind it: what is left of
    # the time from one step's start to the next one's when the step's busy time is taken off
    _, runs = trace_meta.step_runs(traced_result["events"])
    assert want["cycles"] == len(runs) - 1 == 2
    period = (runs[-1][0] - runs[0][0]) / (len(runs) - 1) * 1e-6
    steps = trace_reduce.per_step(traced_result["events"])
    assert sum(idle.values()) == pytest.approx(period - sum(s["busy_s"] for s in steps[:-1]) / 2 * 1e3, rel=2e-3)


def test_find_takes_this_runs_trace_and_checks_its_operation_count(traced_result, capsys):
    meta = trace_meta.find(traced_result)
    assert meta is trace_meta.find(traced_result) and len(meta.ops[PLANE]) == 23619
    # another run's events: the newest file is not their trace
    assert trace_meta.find({"events": trace_reduce.load(SMALL)}) is None
    assert "not this run's trace" in capsys.readouterr().err


@pytest.mark.parametrize("result", [
    {}, {"events": None}, {"events": [("/host:CPU", "python", "bench:train", 0.0, 5.0)]}])
def test_trace_readers_return_nothing_without_a_device_plane(result):
    """A rehearsal on the CPU has no device plane: like trace_step, the
    readers give None and the line's ``rehearsed`` leaves their metrics out."""
    assert trace_meta.find(result) is None
    assert trace_scope.read(result, scopes=["loss"]) is None
    assert trace_idle_in_span.read(result, phases=["fetch"]) is None


def test_a_program_without_the_spans_and_scopes_reports_neither(tmp_path, monkeypatch, capsys):
    """The parent commit writes no distar: spans: nothing to read, no error."""
    where = tmp_path / "o" / "cell" / "trace" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(open(SMALL, "rb").read())
    monkeypatch.setattr(run, "OUT", str(tmp_path / "o"))
    monkeypatch.setattr(run, "T0", time.perf_counter() - 60.0)
    result = {"events": trace_reduce.load(SMALL)}
    assert trace_idle_in_span.read(result, phases=["fetch"]) is None
    # its operations have paths all the same, but none under loss or optimizer:
    # not the names this program writes (the parent's executable, or one that
    # the compile cache kept from before the scopes): nothing is read
    capsys.readouterr()
    assert trace_scope.read(result, scopes=["unnamed"]) is None
    assert trace_scope.read(result, passes=["forward"]) is None
    assert capsys.readouterr().err.count("under loss or optimizer") == 1  # said once per trace


# ------------------------------------------------------------ the manifest
PR22 = ["cache_misses", "compile_backend_s", "data_wait_ms", "device_idle_pct", "device_step_ms",
        "feed_place_ms", "host_callback_ms", "mfu_pct", "program_hbm_gb", "retraces_in_window",
        "step_busy_ms"]


def test_the_manifest_only_gained_per_layer_entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    names = [e["name"] for e in m["per_layer"]]
    assert names[:len(PR22)] == PR22 and len(names) == len(set(names)) >= 40
    assert [w["name"] for w in m["workloads"]] == ["sl_b6t64", "sl_dp4_b24t64", "rl_learn_b6t64"]
    assert [e["name"] for e in m["end_to_end"]] == ["setup_s", "train_frames_per_s"]
    for e in m["per_layer"][len(PR22):]:
        metric_file = cells.load("layer_metrics", e["name"])
        assert metric_file["what"] and e["workloads"] == metric_file["workloads"]
        assert e["layer"] in ("Jitted step", "Device", "Learner run loop", "Feed")
        assert e["moves"] == "train_frames_per_s" and e["better"] == "lower"


@pytest.mark.parametrize("cell", ["sl_dp4_b24t64", "rl_learn_b6t64"])
def test_traced_rehearsal_lists_the_span_fed_metrics(capsys, cell, tmp_path, monkeypatch):
    """--rehearse --trace 1 still prints its line with the spans in the run
    loop (sl_b6t64's is in test_benchmark_rehearse_train); the histogram-fed
    metrics of this PR are among those a measured run would carry, the trace's
    are not."""
    # a directory of its own: test_benchmark_rehearse_train rehearses the same
    # cells from another worker, and both would write <OUT>/<cell>_rehearsal
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    assert run.main(["--workload", cell, "--seed", "3", "--seconds", "2.5", "--trace", "1",
                     "--rehearse"]) == 0
    lines = [ln for ln in capsys.readouterr().out.strip().split("\n") if ln.startswith("{")]
    line = json.loads(lines[-1])
    assert line["metrics"] == {} and line["failed"] == 0
    assert {"loop_pre_step_ms", "loop_dispatch_ms", "loop_fetch_ms", "loop_post_step_ms",
            "feed_pull_ms", "feed_cap_ms", "feed_put_ms", "feed_leaves_per_batch",
            "data_wait_ms", "device_step_ms", "host_callback_ms", "feed_place_ms"} <= set(line["rehearsed"])
    assert not [n for n in line["rehearsed"] if n.startswith(("scope_", "step_", "idle_"))]
