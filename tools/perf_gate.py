#!/usr/bin/env python
"""perf_gate: performance regression gate + bench-trajectory aggregator.

Two commands, both consuming the JSON artifacts the CPU-era harness and
obs.traceview emitted (nothing here measures — this is the layer that READS
the `BENCH_*`/`MULTICHIP_*` files every round produces):

  check       compare a fresh artifact against a committed baseline with a
              noise tolerance. The impossible-timing recheck is a HARD
              precondition: a candidate whose own flop counts say its
              timing beats 1.1x the chip's datasheet peak — or that carries
              an in-band ``suspect``/``suspect_timing`` flag — fails the
              gate no matter how good the comparison looks (no number
              enters README/PERF without passing it; ROADMAP item 5).
              Exit 0 pass / 1 regression / 2 precondition failed.

  trajectory  aggregate the round-over-round artifacts (BENCH_r*.json,
              MULTICHIP_r*.json, ROLLOUT_r*.json,
              artifacts/*_r*.json) into a markdown table, optionally
              rewritten in place between the PERF.md trajectory markers.

  scaling     sweep every committed artifact for forged scaling claims: an
              artifact may say ``scaling_valid: true`` ONLY with recorded
              ``host_cores >= 2`` AND the pinning provenance block the
              tools/pin.py harness writes (``pinning: {pinned: true, ...}``
              — each fleet process on its own core). Anything else —
              including a hand-forged single-core "true" — is refused exit
              2, the same hard-fail class as the impossible-timing recheck.
              The scaling gate is ALSO a ``check`` precondition.

Usage:
  python tools/perf_gate.py check --baseline artifacts/perf_baseline_cpu.json \\
         --candidate fresh.json [--tolerance 0.5]
  python tools/perf_gate.py trajectory [--write PERF.md]
  python tools/perf_gate.py scaling [--artifact one.json]
  python tools/perf_gate.py curve [--tolerance 0.10] [--json]
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distar_tpu.obs.perf import peak_flops  # noqa: E402

TRAJ_BEGIN = "<!-- perf-trajectory:begin -->"
TRAJ_END = "<!-- perf-trajectory:end -->"


# ------------------------------------------------------------------- loading
def load_artifact(path: str) -> dict:
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        # bench-driver JSON-LINES artifact (loadgen/trace_overhead
        # convention: one row per line, the LAST line is the summary) —
        # these used to be skipped silently, which kept e.g. FLEET_r12 out
        # of the trajectory and the scaling sweep
        docs = []
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            docs.append(json.loads(line))
        if not docs:
            raise
        doc = docs[-1]
    # driver wrapper format {n, cmd, rc, tail, parsed} -> the parsed result
    if isinstance(doc, dict) and "parsed" in doc and "tail" in doc:
        return {"_wrapper": doc, **(doc.get("parsed") or {})}
    return doc


def _points(artifact: dict) -> Dict[Tuple, dict]:
    """Comparable sweep points keyed by (kind, batch, unroll, cap, remat).
    Headline-only artifacts key a single ('headline',) point."""
    out: Dict[Tuple, dict] = {}
    for kind in ("sl", "rl", "sl_real"):
        for p in artifact.get(f"{kind}_sweep", []) or []:
            if "step_time_s" not in p and "frames_per_sec" not in p:
                continue  # errored sweep entry
            key = (kind, p.get("batch"), p.get("unroll"),
                   p.get("max_entities"), bool(p.get("remat")))
            out[key] = p
    if not out and isinstance(artifact.get("value"), (int, float)) \
            and artifact.get("value"):
        out[("headline", None, None, None, False)] = {
            "frames_per_sec": artifact["value"], "unit": artifact.get("unit"),
        }
    return out


# --------------------------------------------------------- the physics check
def impossible_timing(artifact: dict) -> List[str]:
    """The impossible-timing recheck over an artifact: any
    point whose max(flops_unoptimized, flops_optimized)/step_time exceeds
    1.1x the named device's datasheet peak is physically impossible. Points
    already flagged in-band (suspect / suspect_timing) count too. Returns
    the list of offences (empty = clean)."""
    offences: List[str] = []
    peak = peak_flops(str(artifact.get("device", "")))
    if artifact.get("suspect") or artifact.get("suspect_timing"):
        offences.append(
            f"artifact flags itself suspect: "
            f"{artifact.get('suspect_reason', 'suspect_timing set')!r}"
        )
    for key, p in _points(artifact).items():
        if p.get("suspect_timing"):
            offences.append(f"{key}: suspect_timing set by the bench recheck")
            continue
        step = p.get("step_time_s")
        flops = max(
            float(p.get("flops_unoptimized", 0.0) or 0.0),
            float(p.get("flops_optimized", 0.0) or 0.0),
            float(p.get("flops_per_step", 0.0) or 0.0),
        )
        if peak and step and flops and flops / step > 1.1 * peak:
            offences.append(
                f"{key}: {flops / step / 1e12:.1f} TFLOP/s implied > 1.1x "
                f"{peak / 1e12:.0f} TFLOP/s peak ({artifact.get('device')})"
            )
    return offences


# -------------------------------------------------------- the scaling check
def scaling_offences(artifact: dict) -> List[str]:
    """Forged-scaling-claim check: ``scaling_valid: true`` is a PHYSICAL
    claim — N fleet processes each held their own core — so it requires (a)
    recorded ``host_cores >= 2`` and (b) the pinning provenance block the
    tools/pin.py harness writes (``pinning.pinned == true`` with its own
    ``host_cores >= 2`` and per-process assignments). A single-core host, a
    refused plan, or a missing block all keep the honest default
    ``scaling_valid: false`` — claiming otherwise is an offence. Artifacts
    that don't claim scaling (false/absent) are always clean."""
    if not artifact.get("scaling_valid"):
        return []
    offences: List[str] = []
    cores = artifact.get("host_cores")
    if not isinstance(cores, int) or cores < 2:
        offences.append(
            f"scaling_valid: true with host_cores={cores!r} — a fleet "
            "cannot scale onto fewer than 2 cores")
    pin = artifact.get("pinning")
    if not isinstance(pin, dict):
        offences.append(
            "scaling_valid: true without a pinning provenance block "
            "(run the fleet under the tools/pin.py harness)")
        return offences
    if not pin.get("pinned"):
        offences.append(
            "scaling_valid: true but pinning.pinned is false "
            f"({pin.get('refused_reason', 'no reason recorded')!r})")
    pin_cores = pin.get("host_cores")
    if not isinstance(pin_cores, int) or pin_cores < 2:
        offences.append(
            f"scaling_valid: true but the pinning block saw "
            f"host_cores={pin_cores!r}")
    if pin.get("pinned") and not pin.get("assignments"):
        offences.append(
            "pinning.pinned is true but no per-process assignments were "
            "recorded")
    return offences


def scaling_sweep(repo: str = _REPO) -> List[Tuple[str, List[str]]]:
    """Every committed artifact with scaling offences: the tier-1 sweep
    (tests/test_perf_gate.py) keeps a forged row from ever landing."""
    paths = sorted(
        glob.glob(os.path.join(repo, "*_r*.json"))
        + glob.glob(os.path.join(repo, "artifacts", "*.json")))
    out: List[Tuple[str, List[str]]] = []
    for path in paths:
        try:
            doc = load_artifact(path)
        except (OSError, ValueError):
            continue
        offences = scaling_offences(doc)
        if offences:
            out.append((path, offences))
    return out


def cmd_scaling(args) -> int:
    if args.artifact:
        offences = scaling_offences(load_artifact(args.artifact))
        hits = [(args.artifact, offences)] if offences else []
        swept = 1
    else:
        hits = scaling_sweep()
        swept = len(glob.glob(os.path.join(_REPO, "*_r*.json"))
                    + glob.glob(os.path.join(_REPO, "artifacts", "*.json")))
    for path, offences in hits:
        for o in offences:
            print(f"FORGED SCALING CLAIM: {os.path.relpath(path, _REPO)}: {o}")
    if hits:
        print("perf_gate scaling: FAIL")
        return 2
    print(f"perf_gate scaling: PASS ({swept} artifacts swept)")
    return 0


# ------------------------------------------------------------------ checking
def compare(baseline: dict, candidate: dict, tolerance: float) -> Tuple[List[str], List[str]]:
    """(regressions, notes). A config regresses when its step time grew (or
    its throughput shrank) by more than ``tolerance`` (0.5 = 50%) over the
    baseline; configs missing from the candidate are notes, not failures
    (budget-truncated sweeps are normal)."""
    regressions: List[str] = []
    notes: List[str] = []
    base_pts, cand_pts = _points(baseline), _points(candidate)
    if not base_pts:
        notes.append("baseline has no comparable points")
    compared = 0
    for key, bp in sorted(base_pts.items(), key=str):
        cp = cand_pts.get(key)
        if cp is None:
            notes.append(f"{key}: missing from candidate (sweep truncated?)")
            continue
        compared += 1
        bs, cs = bp.get("step_time_s"), cp.get("step_time_s")
        if bs and cs and cs > bs * (1.0 + tolerance):
            regressions.append(
                f"{key}: step_time {cs:.4f}s vs baseline {bs:.4f}s "
                f"(+{(cs / bs - 1) * 100:.0f}% > {tolerance * 100:.0f}% tolerance)"
            )
            continue
        bf, cf = bp.get("frames_per_sec"), cp.get("frames_per_sec")
        if bf and cf and cf < bf / (1.0 + tolerance):
            regressions.append(
                f"{key}: {cf:.2f} frames/s vs baseline {bf:.2f} "
                f"(-{(1 - cf / bf) * 100:.0f}% > {tolerance * 100:.0f}% tolerance)"
            )
    # traceview reports compare on device step time
    b_step, c_step = (a.get("step_time_device_us") for a in (baseline, candidate))
    if b_step and c_step:
        compared += 1
        if c_step > b_step * (1.0 + tolerance):
            regressions.append(
                f"trace device step: {c_step:.0f}us vs baseline {b_step:.0f}us"
            )
    if not compared:
        regressions.append("no comparable points between baseline and candidate")
    return regressions, notes


def cmd_check(args) -> int:
    baseline = load_artifact(args.baseline)
    candidate = load_artifact(args.candidate)
    offences = impossible_timing(candidate)
    offences += [f"scaling: {o}" for o in scaling_offences(candidate)]
    if offences:
        for o in offences:
            print(f"PRECONDITION: {o}")
        print("perf_gate: FAIL (impossible-timing/scaling precondition)")
        return 2
    regressions, notes = compare(baseline, candidate, args.tolerance)
    for n in notes:
        print(f"note: {n}")
    for r in regressions:
        print(f"REGRESSION: {r}")
    if regressions:
        print("perf_gate: FAIL")
        return 1
    print(f"perf_gate: PASS (tolerance {args.tolerance * 100:.0f}%)")
    return 0


# ---------------------------------------------------------------- trajectory
def _round_of(path: str) -> str:
    m = re.search(r"_r(\d+)", os.path.basename(path))
    return m.group(1).lstrip("0") or "0" if m else "?"


def _status_of(artifact: dict) -> str:
    if artifact.get("suspect") or artifact.get("suspect_timing"):
        return "SUSPECT (in-band flag)"
    if impossible_timing(artifact):
        return "SUSPECT (impossible timing)"
    if scaling_offences(artifact):
        return "SUSPECT (unproven scaling claim)"
    if artifact.get("metric") is None:  # wrapper with no parsed result line
        return "no result"
    err = artifact.get("error")
    if err:
        return "no result"
    value = artifact.get("value")
    if isinstance(value, (int, float)) and value == 0.0:
        return "no result"
    vs = artifact.get("vs_baseline")
    if isinstance(vs, (int, float)) and vs > 20.0:
        # the b6x64 "109x" class: physically incoherent vs the reference
        # baseline but carrying no flop counts to prove it in-band
        return "SUSPECT (>20x baseline, unverifiable)"
    if artifact.get("device", "").lower().startswith("tpu"):
        return "ok (on-silicon)"
    return "ok (CPU-derived)"


def _multichip_row(path: str, doc: dict) -> Optional[dict]:
    if "multichip" in doc:  # executed-GSPMD scaling case (round 6+)
        return {
            "round": _round_of(path), "artifact": os.path.basename(path),
            "metric": "dp scaling efficiency", "value": doc.get("value"),
            "unit": doc.get("unit", ""), "status": _status_of(doc),
        }
    if "ok" in doc:  # dryrun wrapper format (rounds 1-5)
        return {
            "round": _round_of(path), "artifact": os.path.basename(path),
            "metric": "multichip dryrun", "value": 1.0 if doc.get("ok") else 0.0,
            "unit": f"ok @ {doc.get('n_devices', '?')} devices",
            "status": "ok (structural)" if doc.get("ok") else "no result",
        }
    return None


def collect_trajectory(repo: str = _REPO) -> List[dict]:
    rows: List[dict] = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))
                       + glob.glob(os.path.join(repo, "ROLLOUT_r*.json"))
                       + glob.glob(os.path.join(repo, "REPLAY_SHARD_r*.json"))
                       + glob.glob(os.path.join(repo, "FLEET_r*.json"))
                       + glob.glob(os.path.join(repo, "SHM_r*.json"))
                       + glob.glob(os.path.join(repo, "TRACE_r*.json"))
                       + glob.glob(os.path.join(repo, "DISTILL_r*.json"))
                       + glob.glob(os.path.join(repo, "DYNAMICS_r*.json"))
                       + glob.glob(os.path.join(repo, "ANAKIN_r*.json"))
                       + glob.glob(os.path.join(repo, "ARENA_r*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "perf_baseline*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "dynamics_*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "curves_r*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "rollout_*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "replay_*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "fleet_*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "shm_*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "trace_*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "distill_*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "anakin_*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "arena_*.json"))):
        try:
            doc = load_artifact(path)
        except (OSError, ValueError):
            continue
        rows.append({
            "round": _round_of(path), "artifact": os.path.basename(path),
            "metric": doc.get("metric", "?"), "value": doc.get("value"),
            "unit": doc.get("unit", ""), "status": _status_of(doc),
        })
        curve = doc.get("fleet_curve") or []
        if curve:
            # the serve-fleet artifact carries the capacity sweep in-band;
            # surface the at-capacity shed knee as its own trajectory row
            knee = max(curve, key=lambda r: r.get("level", 0))
            rows.append({
                "round": _round_of(path), "artifact": os.path.basename(path),
                "metric": (f"fleet session shed rate at "
                           f"{knee.get('level')} offered sessions "
                           f"({doc.get('gateways')} gateways)"),
                "value": knee.get("session_shed_rate"), "unit": "",
                "status": _status_of(doc),
            })
        if doc.get("shm_vs_tcp"):
            # the shm-transport artifact carries the three-way ratios
            # in-band; surface wall AND cpu ratios as trajectory rows
            rows.append({
                "round": _round_of(path), "artifact": os.path.basename(path),
                "metric": "shm ring vs framed-TCP loopback, real subprocesses "
                          "(wall clock)",
                "value": doc["shm_vs_tcp"], "unit": "x",
                "status": _status_of(doc),
            })
            if doc.get("shm_vs_tcp_cpu"):
                rows.append({
                    "round": _round_of(path),
                    "artifact": os.path.basename(path),
                    "metric": "shm ring vs framed-TCP loopback "
                              "(cpu-seconds per item, core-count independent)",
                    "value": doc["shm_vs_tcp_cpu"], "unit": "x",
                    "status": _status_of(doc),
                })
        if doc.get("envelope_pct") is not None:
            # a paired on/off overhead artifact (tracing r13, dynamics r16:
            # ab_label says which subsystem was A/B'd): surface the verdict
            # as its own row (the off arm is the comparison baseline)
            rows.append({
                "round": _round_of(path), "artifact": os.path.basename(path),
                "metric": f"{doc.get('ab_label', 'tracing')} on-vs-off "
                          "within the stated "
                          f"{doc.get('envelope_pct'):g}% envelope",
                "value": 1.0 if doc.get("within_envelope") else 0.0,
                "unit": "bool",
                "status": _status_of(doc),
            })
        for family, curve in sorted((doc.get("curves") or {}).items()):
            values = (curve or {}).get("values") or []
            if len(values) >= 2:
                # a committed learning-curve artifact: surface each family's
                # first->last descent; `perf_gate curve` gates it across
                # rounds
                rows.append({
                    "round": _round_of(path),
                    "artifact": os.path.basename(path),
                    "metric": (f"toy-run {family} {values[0]:g} -> "
                               f"{values[-1]:g} over {len(values)} points"),
                    "value": values[-1], "unit": "loss",
                    "status": _status_of(doc),
                })
        toy = (doc.get("distill") or {}).get("toy_run") or {}
        if toy.get("kl_first") is not None:
            # the distill artifact carries the toy-run KL curve in-band;
            # surface the convergence verdict as its own trajectory row
            rows.append({
                "round": _round_of(path), "artifact": os.path.basename(path),
                "metric": (f"distill toy-run KL {toy['kl_first']:g} -> "
                           f"{toy['kl_last']:g} over {toy.get('iters')} iters "
                           f"(monotone={bool(toy.get('monotone_decrease'))})"),
                "value": toy["kl_last"], "unit": "KL",
                "status": _status_of(doc),
            })
        anakin = doc.get("anakin") or {}
        if anakin.get("fused_vs_actor") or anakin.get("fused_vs_host"):
            # the anakin artifact carries both A/Bs in-band; headline the
            # real mock-env actor path (the ROADMAP baseline) and keep the
            # charitable tight-loop floor in the label
            baseline = ("mock-env actor path" if anakin.get("fused_vs_actor")
                        else "one-lane host loop")
            rows.append({
                "round": _round_of(path), "artifact": os.path.basename(path),
                "metric": (f"anakin fused scan vs {baseline}, same policy "
                           f"({anakin.get('batch_lanes')} lanes; "
                           f"tight-loop floor {anakin.get('fused_vs_host')}x; "
                           f"device_pure={bool(anakin.get('device_pure'))})"),
                "value": anakin.get("fused_vs_actor")
                or anakin["fused_vs_host"], "unit": "x",
                "status": _status_of(doc),
            })
        arena = doc.get("arena") or {}
        if arena.get("anchor_relative") is not None:
            # the arena artifact carries the skill ledger in-band; surface
            # the newest generation's anchor-relative rating as its own
            # trajectory row (`perf_gate skill` gates it across rounds)
            rows.append({
                "round": _round_of(path), "artifact": os.path.basename(path),
                "metric": (f"arena anchor-relative rating of newest "
                           f"generation ({arena.get('player', '?')}; "
                           f"{arena.get('matches', '?')} matches vs "
                           f"{arena.get('anchor', 'anchors')})"),
                "value": arena["anchor_relative"], "unit": "elo",
                "status": _status_of(doc),
            })
        fast = doc.get("replay_fast_path") or {}
        if fast.get("vs_tcp_loopback"):
            # the sharded-replay artifact carries the colocated fast-path
            # A/B in-band; surface it as its own trajectory row
            rows.append({
                "round": _round_of(path), "artifact": os.path.basename(path),
                "metric": "replay colocated fast path vs framed-TCP loopback",
                "value": fast["vs_tcp_loopback"], "unit": "x",
                "status": _status_of(doc),
            })
    for path in sorted(glob.glob(os.path.join(repo, "MULTICHIP_r*.json"))
                       + glob.glob(os.path.join(repo, "artifacts", "multichip_*.json"))):
        try:
            doc = load_artifact(path)
        except (OSError, ValueError):
            continue
        row = _multichip_row(path, doc)
        if row:
            rows.append(row)
    rows.sort(key=lambda r: (r["round"].zfill(3), r["artifact"]))
    return rows


def render_trajectory(rows: List[dict]) -> str:
    lines = [
        "| round | artifact | metric | value | unit | status |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        value = r["value"]
        value = f"{value:.3g}" if isinstance(value, (int, float)) else (value or "—")
        lines.append(
            f"| {r['round']} | `{r['artifact']}` | {r['metric']} "
            f"| {value} | {r['unit']} | {r['status']} |"
        )
    return "\n".join(lines)


def collect_curves(repo: str = _REPO) -> Dict[str, List[dict]]:
    """Committed toy-run learning curves by family, one entry per round:
    ``sl_total_loss``/``rl_total_loss`` (and anything else a round adds)
    from ``artifacts/curves_r*.json`` ``curves.<family>.values``, plus
    ``distill_kl`` from the DISTILL artifacts' in-band ``kl_curve``."""
    fams: Dict[str, List[dict]] = {}

    def add(family, path, values):
        values = [float(v) for v in values]
        if len(values) >= 2:
            fams.setdefault(family, []).append({
                "round": _round_of(path),
                "artifact": os.path.basename(path),
                "values": values,
            })

    for path in sorted(glob.glob(os.path.join(repo, "artifacts",
                                              "curves_r*.json"))):
        try:
            doc = load_artifact(path)
        except (OSError, ValueError):
            continue
        for family, curve in (doc.get("curves") or {}).items():
            add(family, path, (curve or {}).get("values") or [])
    for path in sorted(glob.glob(os.path.join(repo, "DISTILL_r*.json"))):
        try:
            doc = load_artifact(path)
        except (OSError, ValueError):
            continue
        toy = (doc.get("distill") or {}).get("toy_run") or {}
        add("distill_kl", path, toy.get("kl_curve") or [])
    for entries in fams.values():
        entries.sort(key=lambda e: (e["round"].zfill(3), e["artifact"]))
    return fams


def curve_verdicts(fams: Dict[str, List[dict]],
                   tolerance: float) -> Tuple[List[dict], List[str]]:
    """Per-family learning-curve gate. Each committed curve must be a real
    descent (finite, last < first); across rounds the NEWEST round's final
    value may not regress past the previous round's final value by more
    than ``tolerance`` (relative, sign-safe for negative RL losses). A
    family with a single round is its own baseline — PASS."""
    verdicts, failures = [], []
    for family, entries in sorted(fams.items()):
        for e in entries:
            values = e["values"]
            if not all(math.isfinite(v) for v in values):
                failures.append(f"{family}: non-finite values in "
                                f"{e['artifact']}")
            elif values[-1] >= values[0]:
                failures.append(
                    f"{family}: curve in {e['artifact']} does not descend "
                    f"({values[0]:g} -> {values[-1]:g})")
        verdict = {
            "family": family,
            "rounds": [e["round"] for e in entries],
            "first": entries[0]["values"][0],
            "last": entries[-1]["values"][-1],
        }
        if len(entries) >= 2:
            base, cand = entries[-2], entries[-1]
            base_last, cand_last = base["values"][-1], cand["values"][-1]
            allowed = base_last + tolerance * max(abs(base_last), 1e-9)
            verdict.update({
                "baseline_round": base["round"], "baseline_last": base_last,
                "candidate_round": cand["round"], "candidate_last": cand_last,
                "allowed": allowed,
                "regressed": cand_last > allowed,
            })
            if cand_last > allowed:
                failures.append(
                    f"{family}: round {cand['round']} final {cand_last:g} "
                    f"regressed past round {base['round']}'s {base_last:g} "
                    f"(allowed {allowed:g} at tolerance {tolerance:g})")
        else:
            verdict["regressed"] = False
            verdict["note"] = "single round: baseline PASS"
        verdicts.append(verdict)
    return verdicts, failures


def cmd_curve(args) -> int:
    fams = collect_curves()
    if not fams:
        print("no committed learning-curve artifacts "
              "(artifacts/curves_r*.json, DISTILL_r*.json)")
        return 1
    verdicts, failures = curve_verdicts(fams, args.tolerance)
    if args.json:
        print(json.dumps({"verdicts": verdicts, "failures": failures},
                         indent=1))
    else:
        for v in verdicts:
            if "candidate_last" in v:
                line = (f"{v['family']}: r{v['baseline_round']} "
                        f"{v['baseline_last']:g} -> r{v['candidate_round']} "
                        f"{v['candidate_last']:g} (allowed {v['allowed']:g})")
            else:
                line = (f"{v['family']}: {v['first']:g} -> {v['last']:g} "
                        f"({v.get('note', '')})")
            print(f"  {'REGRESSED' if v.get('regressed') else 'ok':<10} {line}")
        for f in failures:
            print(f"  FAIL: {f}")
    print("curve gate: PASS" if not failures
          else f"curve gate: FAIL ({len(failures)} offence(s))")
    return 0 if not failures else 1


def collect_skill(repo: str = _REPO) -> List[dict]:
    """Committed arena skill ledgers, one entry per round: the newest
    generation's anchor-relative ELO from ``ARENA_r*.json`` /
    ``artifacts/arena_*.json`` in-band ``arena`` blocks."""
    entries: List[dict] = []
    for path in sorted(glob.glob(os.path.join(repo, "ARENA_r*.json"))
                       + glob.glob(os.path.join(repo, "artifacts",
                                                "arena_*.json"))):
        try:
            doc = load_artifact(path)
        except (OSError, ValueError):
            continue
        arena = doc.get("arena") or {}
        value = arena.get("anchor_relative")
        if value is None:
            continue
        entries.append({
            "round": _round_of(path), "artifact": os.path.basename(path),
            "player": arena.get("player", "?"),
            "matches": arena.get("matches"),
            "value": float(value),
        })
    entries.sort(key=lambda e: (e["round"].zfill(3), e["artifact"]))
    return entries


def skill_verdicts(entries: List[dict],
                   tolerance: float) -> Tuple[List[dict], List[str]]:
    """The skill gate, round-over-round like ``curve``: the NEWEST round's
    anchor-relative rating may not fall more than ``tolerance`` ELO points
    below the previous round's. A single round is its own baseline — PASS.
    Non-finite ratings always fail."""
    failures: List[str] = []
    for e in entries:
        if not math.isfinite(e["value"]):
            failures.append(f"non-finite anchor-relative rating in "
                            f"{e['artifact']}")
    verdicts: List[dict] = []
    if entries:
        verdict = {
            "rounds": [e["round"] for e in entries],
            "first": entries[0]["value"],
            "last": entries[-1]["value"],
            "player": entries[-1]["player"],
        }
        if len(entries) >= 2:
            base, cand = entries[-2], entries[-1]
            allowed = base["value"] - tolerance
            verdict.update({
                "baseline_round": base["round"],
                "baseline_value": base["value"],
                "candidate_round": cand["round"],
                "candidate_value": cand["value"],
                "allowed": allowed,
                "regressed": cand["value"] < allowed,
            })
            if cand["value"] < allowed:
                failures.append(
                    f"skill: round {cand['round']} anchor-relative rating "
                    f"{cand['value']:g} regressed past round "
                    f"{base['round']}'s {base['value']:g} "
                    f"(allowed {allowed:g} at tolerance {tolerance:g} elo)")
        else:
            verdict["regressed"] = False
            verdict["note"] = "single round: baseline PASS"
        verdicts.append(verdict)
    return verdicts, failures


def cmd_skill(args) -> int:
    repo = getattr(args, "repo", "") or _REPO
    entries = collect_skill(repo)
    if not entries:
        print("no committed arena skill ledgers "
              "(ARENA_r*.json, artifacts/arena_*.json)")
        return 1
    verdicts, failures = skill_verdicts(entries, args.tolerance)
    if args.json:
        print(json.dumps({"entries": entries, "verdicts": verdicts,
                          "failures": failures}, indent=1))
    else:
        for e in entries:
            print(f"  r{e['round']:<4} {e['artifact']:<24} "
                  f"{e['player']:<16} anchor-relative={e['value']:g}")
        for v in verdicts:
            if "candidate_value" in v:
                print(f"  gate: r{v['baseline_round']} "
                      f"{v['baseline_value']:g} -> r{v['candidate_round']} "
                      f"{v['candidate_value']:g} (allowed {v['allowed']:g})"
                      f"{'  REGRESSED' if v['regressed'] else ''}")
            else:
                print(f"  gate: {v.get('note', '')}")
        for f in failures:
            print(f"  FAIL: {f}")
    print("skill gate: PASS" if not failures
          else f"skill gate: FAIL ({len(failures)} offence(s))")
    return 0 if not failures else 1


def cmd_trajectory(args) -> int:
    rows = collect_trajectory()
    table = render_trajectory(rows)
    if not args.write:
        print(table)
        return 0
    with open(args.write) as f:
        text = f.read()
    block = f"{TRAJ_BEGIN}\n{table}\n{TRAJ_END}"
    if TRAJ_BEGIN in text and TRAJ_END in text:
        pre, rest = text.split(TRAJ_BEGIN, 1)
        _, post = rest.split(TRAJ_END, 1)
        text = pre + block + post
    else:
        text = text.rstrip() + (
            "\n\n## Bench trajectory (artifact-derived, via tools/perf_gate.py)\n\n"
            f"{block}\n"
        )
    with open(args.write, "w") as f:
        f.write(text)
    print(f"wrote {len(rows)} trajectory rows into {args.write}")
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="command", required=True)
    pc = sub.add_parser("check", help="gate a fresh artifact against a baseline")
    pc.add_argument("--baseline", required=True)
    pc.add_argument("--candidate", required=True)
    pc.add_argument("--tolerance", type=float, default=0.5,
                    help="allowed fractional slowdown before failing "
                         "(0.5 = step time may grow 50%%; CPU-noise sized)")
    pt = sub.add_parser("trajectory", help="round-over-round artifact table")
    pt.add_argument("--write", default="",
                    help="rewrite this file's trajectory block in place "
                         "(e.g. PERF.md); default prints to stdout")
    ps = sub.add_parser("scaling",
                        help="refuse forged scaling_valid claims (exit 2)")
    ps.add_argument("--artifact", default="",
                    help="check one artifact instead of sweeping the repo")
    pu = sub.add_parser("curve",
                        help="learning-curve gate: committed toy-run curves "
                             "must descend and not regress round-over-round")
    pu.add_argument("--tolerance", type=float, default=0.10,
                    help="allowed relative regression of the newest round's "
                         "final value vs the previous round's (default 10%%)")
    pu.add_argument("--json", action="store_true",
                    help="print verdicts as one JSON object")
    pk = sub.add_parser("skill",
                        help="arena skill gate: the newest generation's "
                             "anchor-relative rating must not regress "
                             "round-over-round")
    pk.add_argument("--tolerance", type=float, default=50.0,
                    help="allowed anchor-relative ELO drop vs the previous "
                         "round (default 50 points — jaxenv scenario noise)")
    pk.add_argument("--repo", default="",
                    help="sweep this tree instead of the repo root "
                         "(hermetic tests)")
    pk.add_argument("--json", action="store_true",
                    help="print entries/verdicts as one JSON object")
    args = p.parse_args()
    return {"check": cmd_check, "trajectory": cmd_trajectory,
            "scaling": cmd_scaling, "curve": cmd_curve,
            "skill": cmd_skill}[args.command](args)


if __name__ == "__main__":
    raise SystemExit(main())
