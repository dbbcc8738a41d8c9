"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at
flagship width on one TPU chip, and checks what comes out. A SMOKE, not a
benchmark: the step times it prints are a handful of readings on a cold
machine and are never to be quoted as a rate.

  python chip_smoke.py               one chip: kernels, SL trainer, RL trainer
  python chip_smoke.py --four-chips  four chips: the SL trainer on one device,
                                     on --mesh dp=4 and on --mesh dp=2,fsdp=2

This process never imports jax (a parent that has touched jax holds the
chip): each phase is a child running the real command line, one at a time,
each holding the chip alone, with a time limit. Every check reads the
child's own output: its stdout and the metrics it exports to
``<save-path>/logs/obs/scalars.jsonl``. Any phase that fails, times out,
lands off the TPU, restarts its actor or interprets a Pallas kernel exits
non-zero. The last line of stdout is the contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The children place their compile cache by the one rule of
utils/compile_cache.py (from the environment when it says where), so a
second run against the same directory reports cache hits.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

from distar_tpu.obs.perf import PEAK_FLOPS  # jax-free; exact device_kind keys

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
SL_CONFIG = os.path.join("configs", "sl_flagship_v5e.yaml")
RL_CONFIG = os.path.join("configs", "rl_flagship_v5e.yaml")
ITERS = 5  # 1 compiling step + 4 timed ones; log/export cadence iters//4 = 1
# a cold flagship compile is minutes; the whole run must end inside 1200 s
LIMIT_S = {"kernels": 240, "sl": 420, "rl": 660}
FOUR_CHIP_LIMIT_S = 600
# first-step loss, mesh vs one device: same seed, same batch, bf16 compute;
# only the reduction order differs
LOSS_REL_TOL = 2e-2


class SmokeFailure(Exception):
    pass


def sl_cmd(save_path: str, *extra: str) -> list:
    return [sys.executable, "-u", "-m", "distar_tpu.bin.sl_train",
            "--type", "learner", "--full-model", "--no-supervise",
            "--config", SL_CONFIG, "--iters", str(ITERS), "--platform", "tpu",
            "--save-path", save_path, *extra]


def rl_cmd(save_path: str) -> list:
    return [sys.executable, "-u", "-m", "distar_tpu.bin.rl_train",
            "--type", "all", "--full-model", "--no-supervise",
            "--config", RL_CONFIG, "--iters", str(ITERS), "--platform", "tpu",
            "--save-path", save_path]


def kernels_cmd() -> list:
    return [sys.executable, "-u", os.path.join("tools", "bench_kernels.py"),
            "--platform", "tpu", "--iters", "5"]


def run_phase(name: str, cmd: list, limit_s: float) -> str:
    """Run one child to its end inside ``limit_s``; returns its output. The
    child gets its own process group so a timeout takes its threads' and
    subprocesses' lives with it."""
    os.makedirs(LOG_DIR, exist_ok=True)
    log_path = os.path.join(LOG_DIR, f"{name}.log")
    print(f"[{name}] $ {' '.join(cmd)}", flush=True)
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=limit_s)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    with open(log_path, errors="replace") as f:
        out = f.read()
    if rc != 0:
        tail = "\n".join(out.splitlines()[-40:])
        why = f"timed out after {limit_s} s" if rc is None else f"exit code {rc}"
        raise SmokeFailure(f"[{name}] {why}; end of {log_path}:\n{tail}")
    print(f"[{name}] child exited 0 after {time.monotonic() - t0:.1f} s", flush=True)
    return out


def read_scalars(save_path: str) -> dict:
    """``name -> {step: value}`` from the learner's registry export."""
    path = os.path.join(save_path, "logs", "obs", "scalars.jsonl")
    # keep the evidence: the save path (checkpoints and all) is deleted
    shutil.copy(path, os.path.join(LOG_DIR, f"{os.path.basename(save_path)}.scalars.jsonl"))
    series: dict = {}
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            series.setdefault(rec["name"], {})[rec["step"]] = rec["value"]
    return series


def last(series: dict, name: str, default=None):
    """Last exported value of ``name``, summed over its label sets."""
    hits = [v for k, v in series.items() if k == name or k.startswith(name + "{")]
    if not hits:
        if default is None:
            raise SmokeFailure(f"metric {name} was never exported")
        return default
    return sum(points[max(points)] for points in hits)


def by_label(series: dict, name: str, label: str) -> dict:
    """``label value -> last value`` for every label set of ``name``."""
    out = {}
    for key, points in series.items():
        m = re.fullmatch(re.escape(name) + r"\{(.*)\}", key)
        if m:
            labels = dict(kv.split("=", 1) for kv in m.group(1).split(","))
            out[labels[label]] = points[max(points)]
    return out


def check_device(name: str, device: dict, want_count: int) -> dict:
    print(f"[{name}] device platform={device['platform']} "
          f"kind={device['kind']!r} count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        raise SmokeFailure(f"[{name}] ran on {device['platform']!r}, not the tpu")
    if device["kind"] not in PEAK_FLOPS:
        raise SmokeFailure(
            f"[{name}] unknown device_kind {device['kind']!r}: not in "
            "obs.perf.PEAK_FLOPS, refusing to assume a peak")
    if device["count"] != want_count:
        raise SmokeFailure(
            f"[{name}] {device['count']} devices, this mode needs {want_count}")
    return device


def check_trainer(name: str, save_path: str, want_count: int) -> dict:
    """Everything a trainer phase must show, from its metrics export."""
    s = read_scalars(save_path)
    kinds = by_label(s, "distar_device_count", "kind")
    platforms = by_label(s, "distar_device_count", "platform")
    if len(kinds) != 1:
        raise SmokeFailure(f"[{name}] device gauge missing or ambiguous: {kinds}")
    (kind, count), = kinds.items()
    device = check_device(name, {"platform": next(iter(platforms)), "kind": kind,
                                 "count": int(count)}, want_count)

    hits = last(s, "distar_compile_cache_hits_total", 0.0)
    misses = last(s, "distar_compile_cache_misses_total", 0.0)
    trace_s = sum(points[max(points)] for key, points in s.items()
                  if key.startswith("distar_compile_seconds_total{") and "stage=trace" in key)
    print(f"[{name}] compile: trace_s={trace_s:.1f} "
          f"backend_compile_s={last(s, 'distar_compile_backend_seconds_total'):.1f} "
          f"persistent_cache hits={hits:.0f} misses={misses:.0f} "
          f"-> cache {'HIT' if hits and not misses else 'cold or partial'}", flush=True)

    losses = s.get("distar_learner_loss", {})
    times = next((v for k, v in s.items()
                  if k.startswith("distar_perf_step_seconds{")), {})
    steps = sorted(losses)
    if steps != list(range(1, ITERS + 1)) or sorted(times) != steps:
        raise SmokeFailure(
            f"[{name}] wanted steps 1..{ITERS}, export has losses at {steps} "
            f"and step times at {sorted(times)}")
    print(f"[{name}] step 1 (trace + compile + run): {times[1]:.1f} s "
          f"loss={losses[1]:.6f}", flush=True)
    for k in steps[1:]:
        # the learner's timer ends after the step's log scalars reached the
        # host (device_get), i.e. after the device finished the step
        print(f"[{name}] step {k}: {times[k]:.4f} s loss={losses[k]:.6f}", flush=True)
    if not all(math.isfinite(losses[k]) for k in steps):
        raise SmokeFailure(f"[{name}] non-finite loss: {losses}")
    if len({losses[k] for k in steps[1:]}) < len(steps) - 1:
        raise SmokeFailure(f"[{name}] loss did not change between steps: {losses}")

    peaks = by_label(s, "distar_perf_hbm_peak_bytes", "device")
    if len(peaks) != want_count:
        raise SmokeFailure(f"[{name}] peak_bytes_in_use for {sorted(peaks)}, "
                           f"wanted {want_count} devices")
    print(f"[{name}] peak_bytes_in_use: " + " ".join(
        f"{d}={int(v)}" for d, v in sorted(peaks.items())), flush=True)

    fallbacks = last(s, "distar_pallas_interpret_fallbacks_total", 0.0)
    if fallbacks:
        raise SmokeFailure(f"[{name}] {fallbacks:.0f} pallas_call(s) ran interpret=True")
    return {"device": device, "series": s, "first_loss": losses[1],
            "step_s": [times[k] for k in steps[1:]], "peak_bytes": max(peaks.values())}


def phase_kernels() -> dict:
    out = run_phase("kernels", kernels_cmd(), LIMIT_S["kernels"])
    report = json.loads(out.strip().splitlines()[-1])
    check_device("kernels", report["device"], 1)
    print(f"[kernels] {report['checked']} kernel/shape/dtype/pass cases agree with "
          f"their references; pallas_mode={report['pallas_mode']} "
          f"interpret_fallbacks={report['interpret_fallbacks']}", flush=True)
    print(f"[kernels] device.memory_stats() after them: {report['memory_stats']}",
          flush=True)
    if report["pallas_mode"] != "native" or report["interpret_fallbacks"]:
        raise SmokeFailure("[kernels] a pallas_call ran interpret=True")
    return {"checked": report["checked"]}


def phase_sl(tmp: str) -> dict:
    save = os.path.join(tmp, "sl")
    run_phase("sl", sl_cmd(save), LIMIT_S["sl"])
    r = check_trainer("sl", save, 1)
    return {"device": r["device"], "step_s": r["step_s"], "peak_bytes": r["peak_bytes"]}


def phase_rl(tmp: str) -> dict:
    save = os.path.join(tmp, "rl")
    out = run_phase("rl", rl_cmd(save), LIMIT_S["rl"])
    r = check_trainer("rl", save, 1)
    s = r["series"]
    batches = last(s, "distar_rollout_sample_seconds{backend=inline}_count")
    pushed = last(s, "distar_actor_traj_pushed_total")
    consumed = last(s, "distar_dataloader_batches_total")
    plane = "native" if last(s, "distar_shuttle_native") else "python"
    done = re.search(r"rl_train done: .*actor_restarts=(\d+)", out)
    if not done:
        raise SmokeFailure("[rl] no 'rl_train done' line with the actor restart count")
    print(f"[rl] actor inference batches={batches:.0f} trajectories pushed={pushed:.0f} "
          f"learner batches consumed={consumed:.0f} actor_restarts={done.group(1)} "
          f"shuttle plane={plane}", flush=True)
    if not (batches > 0 and pushed > 0 and consumed >= ITERS):
        raise SmokeFailure("[rl] the actor's trajectories did not reach the learner")
    if int(done.group(1)):
        raise SmokeFailure(f"[rl] the actor loop restarted {done.group(1)} time(s)")
    return {"device": r["device"], "step_s": r["step_s"], "peak_bytes": r["peak_bytes"],
            "actor_batches": batches, "shuttle": plane}


def four_chips(tmp: str) -> dict:
    """The SL trainer at global batch 4 on one device, on dp=4 and on
    dp=2 x fsdp=2 — and nothing else."""
    runs = {}
    for name, mesh in (("one_device", "dp=1"), ("dp4", "dp=4"),
                       ("dp2_fsdp2", "dp=2,fsdp=2")):
        save = os.path.join(tmp, name)
        run_phase(name, sl_cmd(save, "--batch-size", "4", "--mesh", mesh),
                  FOUR_CHIP_LIMIT_S)
        r = check_trainer(name, save, 4)
        r["state"] = by_label(r["series"], "distar_perf_state_bytes", "device")
        r["batch"] = by_label(r["series"], "distar_perf_batch_bytes", "device")
        print(f"[{name}] param+optimizer bytes per device: {r['state']}", flush=True)
        print(f"[{name}] batch bytes per device: {r['batch']}", flush=True)
        runs[name] = r
    one = runs["one_device"]
    if len(one["state"]) != 1 or len(one["batch"]) != 1:
        raise SmokeFailure("[one_device] --mesh dp=1 did not stay on one device")
    for name in ("dp4", "dp2_fsdp2"):
        r = runs[name]
        for what in ("state", "batch"):
            if len(r[what]) != 4 or not all(v > 0 for v in r[what].values()):
                raise SmokeFailure(
                    f"[{name}] {what} shards live on {sorted(r[what])}, wanted "
                    "four different devices")
        rel = abs(r["first_loss"] - one["first_loss"]) / abs(one["first_loss"])
        print(f"[{name}] first-step loss {r['first_loss']:.6f} vs one device "
              f"{one['first_loss']:.6f}: rel diff {rel:.2e} (tolerance "
              f"{LOSS_REL_TOL:.0e})", flush=True)
        if not rel <= LOSS_REL_TOL:
            raise SmokeFailure(f"[{name}] first-step loss disagrees with one device")
    dp4, fsdp = max(runs["dp4"]["state"].values()), max(runs["dp2_fsdp2"]["state"].values())
    print(f"[dp2_fsdp2] param+optimizer bytes per device {int(fsdp)} vs dp4 "
          f"{int(dp4)}", flush=True)
    if not fsdp < dp4:
        raise SmokeFailure("dp=2,fsdp=2 holds no less state per device than dp=4")
    return {"device": one["device"],
            **{n: {"step_s": r["step_s"], "first_loss": r["first_loss"]}
               for n, r in runs.items()}}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the four-chip --mesh path and the one-device "
                        "run it is compared with")
    args = p.parse_args()
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")  # checkpoints: too big to bring back
    t0 = time.monotonic()
    try:
        if args.four_chips:
            phases = {"four_chips": four_chips(tmp)}
        else:
            # kernels first: the cheapest child to find there is no TPU
            phases = {"kernels": phase_kernels(), "sl": phase_sl(tmp),
                      "rl": phase_rl(tmp)}
    except SmokeFailure as e:
        print(f"CHIP SMOKE FAILED after {time.monotonic() - t0:.0f} s: {e}", flush=True)
        return 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    device = next(p["device"] for p in phases.values() if "device" in p)
    print(json.dumps({"smoke": "not a benchmark", "wall_s": round(time.monotonic() - t0, 1),
                      "phases": phases, "claim": None}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
