"""Data-plane micro-bench: serializer + socket shuttle + Adapter throughput.

The reference's feed sustains 300 actors pushing traj-16 windows through its
Adapter TCP plane with lz4-compressed pickle payloads (reference:
distar/ctools/worker/coordinator/adapter.py:66-246,
distar/ctools/utils/file_helper.py:21). This tool quantifies ours:

  * serializer: pickle+zlib-1 vs raw pickle, dumps and loads MB/s, on a
    REAL trajectory payload (fake_rl_batch — the actual wire shape actors
    push);
  * socket plane: serve+fetch round trip over loopback, C++ shuttle vs the
    pure-Python fallback, at trajectory-sized payloads;
  * end-to-end Adapter push/pull through an in-process Coordinator.

Prints a human table and one JSON line. CPU-only (no jax import).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def _mb(n_bytes: int) -> float:
    return n_bytes / 1e6


def bench_serializer(payload, iters: int = 5):
    from distar_tpu.comm.serializer import MAGIC_LZ, MAGIC_ZLIB, dumps, loads

    out = {}
    for compress in (True, False):
        blob = dumps(payload, compress=compress)
        t0 = time.perf_counter()
        for _ in range(iters):
            blob = dumps(payload, compress=compress)
        dt_d = (time.perf_counter() - t0) / iters
        t0 = time.perf_counter()
        for _ in range(iters):
            loads(blob)
        dt_l = (time.perf_counter() - t0) / iters
        # label by the codec that actually ran (the blob magic), not by
        # assumption: with g++ present dumps(compress=True) emits LZ4
        if not compress:
            key = "raw"
        else:
            key = {MAGIC_LZ: "lz4", MAGIC_ZLIB: "zlib1"}.get(blob[:4], "compressed")
        out[key] = {
            "blob_mb": round(_mb(len(blob)), 2),
            "dumps_mb_s": round(_mb(len(blob)) / dt_d, 1),
            "loads_mb_s": round(_mb(len(blob)) / dt_l, 1),
        }
    return out


def bench_shuttle(blob: bytes, iters: int = 10):
    """serve+fetch round trip MB/s over loopback, native vs python."""
    from distar_tpu.comm import shuttle

    results = {}
    impls = {}
    if shuttle.native_available():
        impls["cpp"] = (shuttle.serve, shuttle.fetch)
    impls["python"] = (shuttle._py_serve, shuttle._py_fetch)
    for name, (serve, fetch) in impls.items():
        # warmup
        port = serve(blob, 1, 10_000)
        got = fetch("127.0.0.1", port, 10_000)
        assert got == blob, f"{name} shuttle corrupted the payload"
        t0 = time.perf_counter()
        for _ in range(iters):
            port = serve(blob, 1, 10_000)
            fetch("127.0.0.1", port, 10_000)
        dt = (time.perf_counter() - t0) / iters
        results[name] = {
            "payload_mb": round(_mb(len(blob)), 2),
            "round_trip_ms": round(dt * 1000, 2),
            "mb_s": round(_mb(len(blob)) / dt, 1),
        }
    return results


def bench_adapter(payload, iters: int = 8, compress: bool = True):
    """End-to-end push/pull through an in-process Coordinator (the full
    production path: serialize -> shuttle serve -> coordinator register ->
    ask -> shuttle fetch -> deserialize)."""
    from distar_tpu.comm.adapter import Adapter
    from distar_tpu.comm.coordinator import Coordinator
    from distar_tpu.comm.serializer import dumps

    size = _mb(len(dumps(payload, compress=compress)))
    co = Coordinator()
    push_side = Adapter(coordinator=co, compress=compress)
    pull_side = Adapter(coordinator=co, compress=compress)
    push_side.push("bench", payload)
    pull_side.pull("bench")
    t0 = time.perf_counter()
    for _ in range(iters):
        push_side.push("bench", payload)
        pull_side.pull("bench")
    dt = (time.perf_counter() - t0) / iters
    return {
        "payload_mb": round(size, 2),
        "round_trip_ms": round(dt * 1000, 2),
        "mb_s": round(size / dt, 1),
    }


def main():
    from distar_tpu.comm import shuttle
    from distar_tpu.comm.serializer import dumps
    from distar_tpu.learner.data import fake_rl_batch

    traj_len = 16
    payload = fake_rl_batch(1, traj_len, rng=np.random.default_rng(0))
    raw = dumps(payload, compress=False)
    print(f"payload: 1 actor trajectory window (traj_len={traj_len}), "
          f"{_mb(len(raw)):.1f} MB raw pickle")
    print(f"native shuttle available: {shuttle.native_available()}")

    ser = bench_serializer(payload)
    shut = bench_shuttle(raw)
    compressed_label = next((k for k in ser if k != "raw"), "compressed")
    adap = {
        compressed_label: bench_adapter(payload, compress=True),
        "raw": bench_adapter(payload, compress=False),
    }

    print("\nserializer (pickle):")
    for k, v in ser.items():
        print(f"  {k:6s} blob={v['blob_mb']:7.2f} MB  dumps={v['dumps_mb_s']:8.1f} MB/s  "
              f"loads={v['loads_mb_s']:8.1f} MB/s")
    print("shuttle serve+fetch round trip (loopback):")
    for k, v in shut.items():
        print(f"  {k:6s} {v['payload_mb']:7.2f} MB  {v['round_trip_ms']:8.2f} ms  "
              f"{v['mb_s']:8.1f} MB/s")
    print("adapter end-to-end push+pull (in-process coordinator):")
    for k, v in adap.items():
        print(f"  {k:6s} {v['payload_mb']:7.2f} MB  {v['round_trip_ms']:8.2f} ms  "
              f"{v['mb_s']:8.1f} MB/s")

    print(json.dumps({
        "metric": "data-plane MB/s",
        "serializer": ser,
        "shuttle": shut,
        "adapter": adap,
    }))


if __name__ == "__main__":
    main()
