"""Serve-plane load generator: closed/open loop, throughput + latency tails.

Drives an ``InferenceGateway`` through any of three targets:

  * in-process (default) — a mock-engine gateway built right here; measures
    the batching/session machinery itself with zero network
  * ``--tcp host:port``  — the framed-TCP data plane of a running
    ``bin/serve.py``
  * ``--http host:port`` — the JSON frontend (expect float-inflation
    overhead; this is the showmatch path, not the actor path)

Modes (the canonical load-test shapes):
  * closed   — ``--clients N`` workers each issue the next request the
    moment the previous returns (think-time 0): measures saturated
    throughput and the batch coalescing under full load.
  * open     — requests arrive at ``--rate R`` per second on a fixed
    schedule regardless of completions: measures latency at a given offered
    load and shed behaviour past saturation.
  * sessions — the eval-farm/ladder shape: SESSIONS arrive at ``--rate R``
    per second, each plays ``--requests-per-session`` sequential steps on
    its own sticky session and then ends it (freeing the slot), so
    thousands of distinct sessions can be sustained on one gateway whose
    slot table is far smaller. Arrivals past live capacity shed typed
    (``CapacityError``) — the summary reports the shed RATE, which is the
    eval-farm sizing number.

  * fleet    — the multi-gateway capacity harness (``--mode fleet``):
    spawns ``--gateways`` real gateway SUBPROCESSES (the jax-free
    ``serve.fleet.gateway_proc``, ``--slots`` lanes each — or drives an
    external fleet via ``--tcp a:p,b:p``), mounts the session-affinity
    ``FleetClient`` router over them, and sweeps ``--fleet-levels``
    CONCURRENT resident sessions: each level allocates that many sticky
    sessions fleet-wide (worker threads interleave many live sessions
    each, so concurrency is server-side slot residency, not thread
    count), steps every session ``--requests-per-session`` times and
    ends it. Reports the sessions/gateway distribution and the
    shed-rate curve as levels sweep past fleet slot capacity — the
    numbers a 10k+ session deployment is sized against.

Output: JSON result lines on stdout (the LAST line is the
summary), optionally mirrored to ``--artifact <path>``. A mid-run hot swap
(``--swap-at <frac>``) exercises the registry under load and reports swap
duration + any in-flight disruption (there must be none).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import List, Optional

import numpy as np

sys.path.insert(0, ".")  # runnable as `python tools/loadgen.py` from repo root

from distar_tpu.obs import get_registry  # noqa: E402
from distar_tpu.serve import (  # noqa: E402
    InferenceGateway,
    MockModelEngine,
    ServeClient,
    ShedError,
)


class _TraceTap:
    """Per-run trace bookkeeping (``--trace``): mints a root span per
    request, finishes it with the outcome, and remembers the trace_ids of
    the slowest and shedded requests so the summary links straight to
    retrievable waterfalls (``opsctl trace --id <id>``)."""

    def __init__(self, enabled: bool):
        self.enabled = bool(enabled)
        # per-thread buckets, merged at summary time: the tap must not add
        # a contended lock to every request of the very bench that measures
        # tracing overhead
        self._local = threading.local()
        self._buckets: List[dict] = []
        self._buckets_lock = threading.Lock()

    def _bucket(self) -> dict:
        b = getattr(self._local, "b", None)
        if b is None:
            b = self._local.b = {"ok": [], "shed": []}
            with self._buckets_lock:
                self._buckets.append(b)
        return b

    def mint(self, session: str):
        if not self.enabled:
            return None
        from distar_tpu.obs import start_trace

        return start_trace("loadgen_request", session=session)

    def done(self, ctx, dt: Optional[float] = None, outcome: str = "ok") -> None:
        if ctx is None:
            return
        from distar_tpu.obs import finish_trace

        finish_trace(ctx, "loadgen_done", outcome=outcome)
        b = self._bucket()
        if outcome == "ok" and dt is not None:
            ok = b["ok"]
            ok.append((dt, ctx["trace_id"]))
            if len(ok) > 4096:  # keep the tail bounded mid-run
                ok.sort(key=lambda p: -p[0])
                del ok[256:]
        elif outcome == "shed":
            shed = b["shed"]
            shed.append(ctx["trace_id"])
            del shed[:-16]

    def summary(self) -> dict:
        if not self.enabled:
            return {}
        with self._buckets_lock:
            buckets = list(self._buckets)
        ok = [p for b in buckets for p in b["ok"]]
        shed = [t for b in buckets for t in b["shed"]]
        top = sorted(ok, key=lambda p: -p[0])[:5]
        return {"slowest_traces": [
            {"trace_id": t, "latency_s": round(d, 6)} for d, t in top],
            "shed_traces": shed[-5:]}


class _Stats:
    def __init__(self):
        self.lat: List[float] = []
        self.ok = 0
        self.shed = 0
        self.errors = 0
        self._lock = threading.Lock()

    def record(self, dt: Optional[float], kind: str) -> None:
        with self._lock:
            if kind == "ok":
                self.ok += 1
                self.lat.append(dt)
            elif kind == "shed":
                self.shed += 1
            else:
                self.errors += 1

    def quantile(self, q: float) -> float:
        with self._lock:
            if not self.lat:
                return 0.0
            return float(np.quantile(np.asarray(self.lat), q))


def _make_obs(i: int) -> dict:
    return {"x": np.full((4, 4), float(i % 7), dtype=np.float32)}


class _InprocTarget:
    def __init__(self, slots: int, delay_s: float, max_delay_s: float, capacity: int,
                 idle_ttl_s: float = 300.0):
        self.engine = MockModelEngine(slots, params={"version": "v1", "bias": 0.0},
                                      delay_s=delay_s)
        self.gateway = InferenceGateway(
            self.engine, max_delay_s=max_delay_s, queue_capacity=capacity,
            idle_ttl_s=idle_ttl_s,
        ).start()
        self.gateway.load_version("v1", params={"version": "v1", "bias": 0.0},
                                  activate=True)

    def act(self, session: str, obs, timeout_s: float, trace=None):
        from distar_tpu.obs import wire_ctx

        return self.gateway.act(session, obs, timeout_s,
                                trace=wire_ctx(trace) if trace else None)

    def end(self, session: str) -> None:
        self.gateway.end_session(session)

    def swap(self) -> None:
        self.gateway.load_version("v2", params={"version": "v2", "bias": 1.0},
                                  activate=True)

    def close(self) -> None:
        self.gateway.drain_and_stop()


class _TcpTarget:
    def __init__(self, addr: str):
        host, port = addr.rsplit(":", 1)
        self._mk = lambda: ServeClient(host, int(port))
        self._local = threading.local()

    def _client(self) -> ServeClient:
        c = getattr(self._local, "c", None)
        if c is None:
            c = self._local.c = self._mk()
        return c

    def act(self, session: str, obs, timeout_s: float, trace=None):
        return self._client().act(session, obs, timeout_s, trace=trace)

    def end(self, session: str) -> None:
        self._client().end(session)

    def swap(self) -> None:
        self._client().load("loadgen-swap", params={"version": "loadgen-swap"},
                            activate=True)

    def close(self) -> None:
        pass


class _HttpTarget:
    def __init__(self, addr: str):
        self._base = f"http://{addr}/serve"

    def _post(self, route: str, body: dict, headers: Optional[dict] = None) -> dict:
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            f"{self._base}/{route}", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json", **(headers or {})},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                out = json.loads(resp.read())
        except urllib.error.HTTPError as e:
            # the drain contract answers 503 with the typed wire body
            out = json.loads(e.read() or b"{}")
        if out.get("code") != 0:
            if out.get("shed"):
                raise ShedError(out.get("error", ""))
            raise RuntimeError(out.get("error") or out.get("info"))
        return out["info"]

    def act(self, session: str, obs, timeout_s: float, trace=None):
        headers = {}
        if trace is not None:
            from distar_tpu.obs import format_traceparent

            tp = format_traceparent(trace)
            if tp:
                headers["traceparent"] = tp
        return self._post("act", {
            "session_id": session,
            "obs": {k: np.asarray(v).tolist() for k, v in obs.items()},
            "timeout_s": timeout_s,
        }, headers=headers)

    def end(self, session: str) -> None:
        self._post("end", {"session_id": session})

    def swap(self) -> None:
        raise RuntimeError("hot swap over HTTP needs a checkpoint source; use --tcp")

    def close(self) -> None:
        pass


def emit(line: dict, artifact_lines: List[dict]) -> None:
    print(json.dumps(line), flush=True)
    artifact_lines.append(line)


# --------------------------------------------------------------- fleet mode
def _spawn_gateway_fleet(n: int, slots: int, delay_s: float):
    """``n`` real mock-gateway subprocesses (jax-free gateway_proc — own
    GIL, real sockets). Returns ``(procs, addrs)``; closing a proc's stdin
    reaps it (the replay bench fleet idiom)."""
    import subprocess

    procs, addrs = [], []
    for _ in range(n):
        cmd = [sys.executable, "-m", "distar_tpu.serve.fleet.gateway_proc",
               "--port", "0", "--http-port", "0", "--slots", str(slots),
               "--mock-delay-s", str(delay_s)]
        proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, text=True)
        parts = proc.stdout.readline().split()
        if len(parts) < 4 or parts[0] != "SERVE-GATEWAY":
            raise RuntimeError(f"gateway failed to start: {parts}")
        addrs.append(f"{parts[1]}:{parts[2]}")
        procs.append(proc)
    return procs, addrs


def _reap_gateway_fleet(procs) -> None:
    for proc in procs:
        try:
            proc.stdin.close()
            proc.wait(timeout=10)
        except Exception:
            proc.kill()


def run_fleet_loadgen(
    gateways: int = 3,
    slots: int = 512,
    fleet_levels: str = "",
    fleet_workers: int = 32,
    requests_per_session: int = 4,
    mock_delay_s: float = 0.0,
    timeout_s: float = 10.0,
    tcp: Optional[str] = None,
    artifact: Optional[str] = None,
    trace: bool = False,
) -> dict:
    """The multi-gateway capacity harness (``--mode fleet``); importable —
    the fleet smoke test and the FLEET_r* artifact runs call this. Returns
    the summary dict (= last stdout JSON line), which carries the in-band
    honesty flags (``host_cores``, ``scaling_valid``): on a small CI host
    the whole fleet time-shares the cores, so the curve proves the routed
    fleet EXECUTES at each level, not that it scales."""
    from distar_tpu.fleet import pinning
    from distar_tpu.serve.fleet import FleetClient, GatewayMap

    host_cores = pinning.host_cores()
    if tcp:
        procs, addrs = [], [a.strip() for a in tcp.split(",") if a.strip()]
        # an external fleet's pids are unknown — pinning cannot be claimed
        pin_prov = pinning.PinPlan(
            pinned=False, host_cores=host_cores,
            refused_reason="external --tcp fleet: member pids unknown to "
                           "the harness").provenance()
    else:
        procs, addrs = _spawn_gateway_fleet(gateways, slots, mock_delay_s)
        # the core-pinning harness: each gateway on its own core, the
        # driving client on the reserved remainder — or an explicit refusal
        # that keeps scaling_valid false in-band on small hosts
        pin_prov = pinning.pin_fleet([p.pid for p in procs], reserve_client=1)
    capacity = slots * len(addrs)
    if fleet_levels:
        levels = [int(x) for x in fleet_levels.split(",") if x.strip()]
    else:
        # sweep up THROUGH fleet capacity and past it: the shed knee is
        # the measurement
        levels = sorted({max(1, capacity // 6), max(1, capacity // 2),
                         capacity, capacity + max(1, capacity // 4)})
    artifact_lines: List[dict] = []
    tap = _TraceTap(trace)
    from distar_tpu.serve.fleet import FleetRouter

    # ONE router (pins, migration accounting, down-list) shared by
    # per-worker FleetClients: a ServeClient holds one connection with one
    # request in flight, so per-worker clients are what lets W requests
    # ride the wire concurrently while affinity state stays coherent
    router = FleetRouter(GatewayMap(addrs))
    clients = [FleetClient(router=router, timeout_s=timeout_s)
               for _ in range(fleet_workers)]
    obs = _make_obs(0)
    curve: List[dict] = []
    try:
        for level in levels:
            stats = _Stats()
            shed_arrival = [0]
            live_sessions: List[List[str]] = [[] for _ in range(fleet_workers)]
            lock = threading.Lock()
            # workers interleave their share of the level's sessions so all
            # admitted sessions are RESIDENT (slot held, carry live) at once
            arrived = threading.Barrier(fleet_workers + 1)
            sampled = threading.Barrier(fleet_workers + 1)

            def traced_act(fc, sid: str) -> str:
                ctx = tap.mint(sid)
                t0 = time.perf_counter()
                try:
                    fc.act(sid, obs, timeout_s, trace=ctx)
                    dt = time.perf_counter() - t0
                    stats.record(dt, "ok")
                    tap.done(ctx, dt, "ok")
                    return "ok"
                except ShedError:
                    stats.record(None, "shed")
                    tap.done(ctx, outcome="shed")
                    return "shed"
                except Exception:
                    stats.record(None, "error")
                    tap.done(ctx, outcome="error")
                    return "error"

            def worker(w: int, sids: List[str]) -> None:
                fc = clients[w]
                mine = live_sessions[w]
                for sid in sids:  # arrival pass: allocate the sticky slot
                    kind = traced_act(fc, sid)
                    if kind == "ok":
                        mine.append(sid)
                    elif kind == "shed":
                        with lock:
                            shed_arrival[0] += 1
                arrived.wait()
                sampled.wait()  # main thread reads live residency here
                for _step in range(max(requests_per_session - 1, 0)):
                    for sid in mine:
                        traced_act(fc, sid)
                for sid in mine:
                    try:
                        fc.end(sid)
                    except Exception:
                        pass

            sids = [f"fleet-{level}-{i}" for i in range(level)]
            shares = [sids[w::fleet_workers] for w in range(fleet_workers)]
            t_start = time.perf_counter()
            threads = [threading.Thread(target=worker, args=(w, shares[w]))
                       for w in range(fleet_workers)]
            for t in threads:
                t.start()
            arrived.wait()
            # every admitted session now holds a slot somewhere: measure
            # true server-side residency + the per-gateway distribution
            per_gateway = dict(router.stats()["pins_per_gateway"])
            resident = sum(len(m) for m in live_sessions)
            sampled.wait()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t_start
            total = stats.ok + stats.shed + stats.errors
            row = {
                "level": level,
                "concurrent_resident": resident,
                "sessions_per_gateway": per_gateway,
                "shed_at_arrival": shed_arrival[0],
                "session_shed_rate": round(shed_arrival[0] / max(level, 1), 4),
                "shed_rate": round(stats.shed / max(total, 1), 4),
                "errors": stats.errors,
                "req_per_s": round(stats.ok / max(elapsed, 1e-9), 2),
                "latency_p50_s": round(stats.quantile(0.5), 6),
                "latency_p99_s": round(stats.quantile(0.99), 6),
                "elapsed_s": round(elapsed, 3),
            }
            curve.append(row)
            emit({"metric": "fleet level", **row}, artifact_lines)
    finally:
        for fc in clients:
            fc.close()
        _reap_gateway_fleet(procs)
    best = max((r["concurrent_resident"] for r in curve), default=0)
    snap = get_registry().snapshot()
    summary = {
        "metric": "serve fleet concurrent resident sessions "
                  "(mock gateways, loopback)",
        "value": best,
        "unit": "sessions",
        "mode": "fleet",
        "device": "cpu",
        "cpu_derived": True,
        "host_cores": host_cores,
        # scaling_valid is now a PROVEN claim: true only when the pin
        # harness actually gave every gateway its own core (provenance
        # below, verified by perf_gate's scaling gate); on a smaller host
        # the curve still proves routed capacity executes, flagged false
        "scaling_valid": pinning.scaling_valid(pin_prov,
                                               min_cores=len(addrs) + 1),
        "pinning": pin_prov,
        "gateways": len(addrs),
        "slots_per_gateway": slots,
        "fleet_slot_capacity": capacity,
        "requests_per_session": requests_per_session,
        "fleet_curve": curve,
        "migrations": snap.get("distar_fleet_session_migrations_total", 0.0),
        "errors_total": sum(r["errors"] for r in curve),
        # --trace: the bench artifact links straight to retrievable
        # waterfalls (opsctl trace --id <trace_id>)
        **tap.summary(),
    }
    emit(summary, artifact_lines)
    if artifact:
        with open(artifact, "w") as f:
            for line in artifact_lines:
                f.write(json.dumps(line) + "\n")
    return summary


def run_loadgen(
    mode: str = "closed",
    clients: int = 8,
    rate: float = 200.0,
    duration_s: float = 5.0,
    requests_per_client: int = 0,
    requests_per_session: int = 8,
    slots: int = 8,
    mock_delay_s: float = 0.002,
    max_delay_s: float = 0.005,
    queue_capacity: int = 256,
    idle_ttl_s: float = 300.0,
    timeout_s: float = 5.0,
    swap_at: float = 0.0,
    tcp: Optional[str] = None,
    http: Optional[str] = None,
    artifact: Optional[str] = None,
    gateways: int = 3,
    fleet_levels: str = "",
    fleet_workers: int = 32,
    trace: bool = False,
) -> dict:
    """Importable driver (the slow soak test calls this). Returns the
    summary dict that is also the last stdout JSON line."""
    assert mode in ("closed", "open", "sessions", "fleet")
    if mode == "fleet":
        return run_fleet_loadgen(
            gateways=gateways, slots=slots, fleet_levels=fleet_levels,
            fleet_workers=fleet_workers,
            requests_per_session=requests_per_session,
            mock_delay_s=mock_delay_s, timeout_s=timeout_s, tcp=tcp,
            artifact=artifact, trace=trace)
    if tcp:
        target = _TcpTarget(tcp)
    elif http:
        target = _HttpTarget(http)
    else:
        target = _InprocTarget(slots, mock_delay_s, max_delay_s, queue_capacity,
                               idle_ttl_s=idle_ttl_s)
    stats = _Stats()
    tap = _TraceTap(trace)
    artifact_lines: List[dict] = []
    stop_at = time.perf_counter() + duration_s
    swapped = threading.Event()

    def one(session: str, i: int) -> None:
        ctx = tap.mint(session)
        t0 = time.perf_counter()
        try:
            target.act(session, _make_obs(i), timeout_s, trace=ctx)
            dt = time.perf_counter() - t0
            stats.record(dt, "ok")
            tap.done(ctx, dt, "ok")
        except ShedError:
            stats.record(None, "shed")
            tap.done(ctx, outcome="shed")
        except Exception:
            stats.record(None, "error")
            tap.done(ctx, outcome="error")

    def maybe_swap(done_frac: float) -> None:
        if swap_at and done_frac >= swap_at and not swapped.is_set():
            swapped.set()
            t0 = time.perf_counter()
            target.swap()
            emit({"metric": "serve_swap_issue", "value": time.perf_counter() - t0,
                  "unit": "s"}, artifact_lines)

    sessions_started = [0]
    sessions_completed = [0]
    sessions_shed = [0]
    sess_lock = threading.Lock()

    def session_life(n: int) -> None:
        """One eval-farm session: arrive, play ``requests_per_session``
        sequential steps on a sticky session, end it (freeing the slot). A
        shed at ARRIVAL (capacity) abandons the session — that's the number
        the farm sizes against; a shed mid-session retries briefly."""
        sid = f"farm-{n}"
        with sess_lock:
            sessions_started[0] += 1
        i = 0
        while i < requests_per_session:
            ctx = tap.mint(sid)
            t0 = time.perf_counter()
            try:
                target.act(sid, _make_obs(i), timeout_s, trace=ctx)
                dt = time.perf_counter() - t0
                stats.record(dt, "ok")
                tap.done(ctx, dt, "ok")
                i += 1
            except ShedError:
                stats.record(None, "shed")
                tap.done(ctx, outcome="shed")
                if i == 0:  # no slot for this session: the farm is full
                    with sess_lock:
                        sessions_shed[0] += 1
                    return
                time.sleep(0.01)
            except Exception:
                stats.record(None, "error")
                tap.done(ctx, outcome="error")
                return
        try:
            target.end(sid)
        except Exception:
            pass
        with sess_lock:
            sessions_completed[0] += 1

    t_start = time.perf_counter()
    if mode == "closed":
        def worker(w: int) -> None:
            session = f"loadgen-{w}"
            i = 0
            while time.perf_counter() < stop_at or (
                requests_per_client and i < requests_per_client
            ):
                if requests_per_client and i >= requests_per_client:
                    break
                one(session, i)
                i += 1
                maybe_swap((time.perf_counter() - t_start) / duration_s)
                if not requests_per_client and time.perf_counter() >= stop_at:
                    break

        threads = [threading.Thread(target=worker, args=(w,)) for w in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    else:  # open / sessions: fixed arrival schedule, unbounded worker threads
        period = 1.0 / max(rate, 1e-9)
        threads = []
        i = 0
        next_fire = time.perf_counter()
        while time.perf_counter() < stop_at:
            now = time.perf_counter()
            if now < next_fire:
                time.sleep(min(next_fire - now, 0.01))
                continue
            if mode == "sessions":
                t = threading.Thread(target=session_life, args=(i,))
            else:
                session = f"loadgen-{i % max(slots, 1)}"
                t = threading.Thread(target=one, args=(session, i))
            t.start()
            threads.append(t)
            i += 1
            next_fire += period
            maybe_swap((now - t_start) / duration_s)
        for t in threads:
            t.join(timeout_s * (requests_per_session if mode == "sessions" else 1) + 1.0)
    elapsed = time.perf_counter() - t_start
    target.close()

    total = stats.ok + stats.shed + stats.errors
    summary = {
        "metric": "serve_throughput",
        "value": round(stats.ok / max(elapsed, 1e-9), 2),
        "unit": "req/s",
        "mode": mode,
        "ok": stats.ok,
        "shed": stats.shed,
        "errors": stats.errors,
        "total": total,
        "elapsed_s": round(elapsed, 3),
        "latency_p50_s": round(stats.quantile(0.5), 6),
        "latency_p99_s": round(stats.quantile(0.99), 6),
        # the eval-farm sizing number: what fraction of offered work the
        # gateway refused (typed sheds / everything offered)
        "shed_rate": round(stats.shed / max(total, 1), 4),
        # --trace: trace_ids of the slowest/shedded requests, retrievable
        # as waterfalls via opsctl trace --id <id>
        **tap.summary(),
    }
    if mode == "sessions":
        summary["sessions"] = {
            "started": sessions_started[0],
            "completed": sessions_completed[0],
            "shed_at_arrival": sessions_shed[0],
            "requests_per_session": requests_per_session,
            "session_shed_rate": round(
                sessions_shed[0] / max(sessions_started[0], 1), 4),
        }
    if tcp is None and http is None:
        # in-process: the serve metrics live in OUR registry — report the
        # coalescing the acceptance criteria care about
        snap = get_registry().snapshot()
        occ_count = snap.get("distar_serve_batch_occupancy_count", 0.0)
        occ_sum = snap.get("distar_serve_batch_occupancy_sum", 0.0)
        summary["mean_batch_occupancy"] = round(occ_sum / occ_count, 3) if occ_count else 0.0
        summary["swap_p99_s"] = snap.get("distar_serve_swap_duration_seconds_p99", 0.0)
    for q, name in ((0.5, "serve_latency_p50"), (0.99, "serve_latency_p99")):
        emit({"metric": name, "value": stats.quantile(q), "unit": "s"}, artifact_lines)
    emit(summary, artifact_lines)
    if artifact:
        with open(artifact, "w") as f:
            for line in artifact_lines:
                f.write(json.dumps(line) + "\n")
    return summary


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", choices=("closed", "open", "sessions", "fleet"),
                   default="closed")
    p.add_argument("--gateways", type=int, default=3,
                   help="fleet mode: gateway subprocesses to spawn (ignored "
                        "with --tcp, which may name an external fleet "
                        "'a:p,b:p')")
    p.add_argument("--fleet-levels", default="",
                   help="fleet mode: comma list of concurrent-resident-"
                        "session levels to sweep (default: auto up through "
                        "fleet slot capacity and past it)")
    p.add_argument("--fleet-workers", type=int, default=32,
                   help="fleet mode: driver threads (each interleaves many "
                        "live sessions; concurrency = resident slots, not "
                        "threads)")
    p.add_argument("--clients", type=int, default=8, help="closed-loop workers")
    p.add_argument("--rate", type=float, default=200.0,
                   help="open-loop request arrivals/s; sessions mode: "
                        "session arrivals/s")
    p.add_argument("--duration-s", type=float, default=5.0)
    p.add_argument("--requests-per-client", type=int, default=0,
                   help="closed loop: stop after N requests instead of duration")
    p.add_argument("--requests-per-session", type=int, default=8,
                   help="sessions mode: steps each arriving session plays "
                        "before ending (eval-farm episode length)")
    p.add_argument("--slots", type=int, default=8, help="in-process mock slots")
    p.add_argument("--mock-delay-s", type=float, default=0.002)
    p.add_argument("--max-delay-s", type=float, default=0.005)
    p.add_argument("--queue-capacity", type=int, default=256)
    p.add_argument("--idle-ttl-s", type=float, default=300.0,
                   help="in-process gateway session idle eviction")
    p.add_argument("--timeout-s", type=float, default=5.0)
    p.add_argument("--swap-at", type=float, default=0.0,
                   help="hot-swap when this fraction of the run has elapsed (0=off)")
    p.add_argument("--tcp", help="host:port of a running serve TCP frontend")
    p.add_argument("--http", help="host:port of a running serve HTTP frontend")
    p.add_argument("--artifact", help="also write the JSON lines to this path")
    p.add_argument("--trace", action="store_true",
                   help="mint a distributed-trace span per request; the "
                        "summary then names the trace_ids of the slowest "
                        "and shedded requests (opsctl trace --id <id>)")
    p.add_argument("--coordinator", default="",
                   help="with --trace: ship this process's tail-sampled "
                        "client spans (and telemetry) to the coordinator at "
                        "host:port, so the summary's trace_ids resolve to "
                        "FULL waterfalls — client span joined with the "
                        "gateway spans the fleet ships — via opsctl trace")
    p.add_argument("--no-trace-minting", action="store_true",
                   help="force span minting OFF process-wide (the overhead "
                        "A/B posture — also disables server-side joins in "
                        "the in-process gateway)")
    args = p.parse_args()
    if args.no_trace_minting:
        from distar_tpu.obs import set_tracing

        set_tracing(False)
    shipper = None
    if args.coordinator and args.trace:
        from distar_tpu.obs import TelemetryShipper

        chost, _, cport = args.coordinator.rpartition(":")
        shipper = TelemetryShipper(
            source=f"loadgen:{os.getpid()}",
            coordinator_addr=(chost or "127.0.0.1", int(cport)),
            interval_s=1.0).start()
    kwargs = {k.replace("-", "_"): v for k, v in vars(args).items()}
    kwargs.pop("no_trace_minting", None)
    kwargs.pop("coordinator", None)
    try:
        run_loadgen(**kwargs)
    finally:
        if shipper is not None:
            shipper.stop()
            try:
                # final flush: the tail kept since the last tick must reach
                # the broker before this short-lived process exits
                shipper.ship_once()
            except Exception:
                pass


if __name__ == "__main__":
    main()
