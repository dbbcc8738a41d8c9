"""Record a few steps of the real SL learner under the profiler, for the
tests of the scope and span readers.

The configuration's tiny preset (with ``remat`` on, so that the recompute
pass exists), the program's own ``SLLearner.run`` with its feeder thread,
``--steps`` steps traced after three warm-up steps, python tracer off. The
trace goes to ``chiprun_out/<name>/`` so that a chip call brings it back;
the full ``.xplane.pb`` is 15 MB (HLO text as event names, runtime threads),
so ``slim`` writes beside it what the benchmark's reduction reads and no
more: the device planes' ``XLA Ops`` and ``XLA Modules`` lines with event
names cut to 100 characters and the ``tf_op`` and ``hlo_category`` stats, and
the host threads' ``distar:`` spans. That file is committed under
``tests/benchmark/data`` with the values the readers must give beside it.

  python -m benchmark.tools.record_step_trace [--name step_trace] [--cell sl_b6t64]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

WARMUP = 3
NAME_CHARS = 100  # enough for trace_reduce.short_name


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes or str length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def slim(path: str, out: str) -> None:
    """Re-encode ``path`` with only what ``trace_reduce.load`` and
    ``trace_meta.parse`` read (field numbers: ``trace_meta``'s docstring)."""
    from benchmark import trace_meta, trace_reduce

    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = b""
    for f, _, plane in trace_meta._fields(space):
        if f != 1:
            continue
        name = next((trace_meta._text(v) for g, _, v in trace_meta._fields(plane) if g == 2), "")
        device = bool(trace_reduce.DEVICE_PLANE.match(name))
        if not device and not name.startswith("/host:"):
            continue
        lines, names, stats = trace_meta._plane(
            plane, (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE) if device else None,
            "" if device else trace_meta.SPAN_PREFIX)
        body = _field(2, name)
        used = sorted({mid for _, _, rows in lines for mid, _, _ in rows})
        for i, stat in enumerate(trace_meta.STATS, 1):
            body += _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, stat)))
        for mid in used:
            meta = _field(1, mid) + _field(2, names[mid][:NAME_CHARS])
            for i, stat in enumerate(trace_meta.STATS, 1):
                if stat in stats[mid]:
                    meta += _field(5, _field(1, i) + _field(5, stats[mid][stat]))
            body += _field(4, _field(1, mid) + _field(2, meta))
        for line_name, line_id, rows in lines:
            if not rows:
                continue
            t0 = int(min(a for _, a, _ in rows))
            line = _field(1, line_id) + _field(2, line_name) + _field(3, t0)
            for mid, a, b in rows:
                line += _field(4, _field(1, mid) + _field(2, round((a - t0) * 1000))
                               + _field(3, round((b - a) * 1000)))
            body += _field(3, line)
        planes += _field(1, body)
    with open(out, "wb") as f:
        f.write(planes)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--name", default="step_trace")
    p.add_argument("--cell", default="sl_b6t64")
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--cpu", action="store_true", help="no chip: a trace without a device plane")
    args = p.parse_args()

    from benchmark import cells, device, trace_meta, trace_reduce
    from benchmark.drivers import learner as driver

    cell = cells.load_cell(args.cell)
    device.require(cell["chips"], rehearse=args.cpu)
    import jax

    from distar_tpu.learner.hooks import LambdaHook
    from distar_tpu.parallel import MeshSpec, make_mesh

    size = driver.sized(cell, rehearse=True)
    model = dict(size["model"], remat=True, dtype=cell["config"]["as_run"]["model"]["dtype"])
    out = os.path.join(ROOT, "chiprun_out", args.name)
    shutil.rmtree(out, ignore_errors=True)
    spec = MeshSpec.parse(cell["mesh"])
    mesh = make_mesh(spec, jax.devices()[: spec.dp * spec.fsdp * spec.tp * spec.sp])
    learner = driver.build_learner(cell, model, size["traffic"], args.seed, mesh,
                                   os.path.join(out, "run"))
    gen = cells.module("gen", cell["traffic"]["generator"])
    learner.set_dataloader(gen.cycle(gen.build(args.seed, size["traffic"], model_cfg=learner.model_cfg)))

    def hook(lr) -> None:
        k = lr.last_iter.val
        if k == WARMUP:
            device.start_trace(os.path.join(out, "trace"))
        elif k == WARMUP + args.steps:
            jax.profiler.stop_trace()
            lr.request_stop()

    learner.hooks.add(LambdaHook("record", "after_iter", hook, priority=5))
    try:
        learner.run(max_iterations=10 ** 6)
    finally:
        if hasattr(learner._dataloader, "close"):
            learner._dataloader.close()
    shutil.rmtree(os.path.join(out, "run"), ignore_errors=True)

    path = trace_reduce.find_xplane(os.path.join(out, "trace"))
    slim(path, os.path.join(out, args.name + ".xplane.pb"))
    meta = trace_meta.parse(path)
    dev = jax.devices()[0]
    print(json.dumps({
        "device": [dev.platform, dev.device_kind, len(jax.devices())],
        "xplane": os.path.relpath(path, ROOT), "bytes": os.path.getsize(path),
        "device_ops": {plane: len(ops) for plane, ops in meta.ops.items()},
        "spans": sorted({s.name for s in meta.spans}),
        "threads": sorted({s.thread for s in meta.spans}),
    }))


if __name__ == "__main__":
    main()
