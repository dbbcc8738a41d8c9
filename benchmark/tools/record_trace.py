"""Record a small trace on the machine this runs on, for the reduction's test.

A few runs of a small jitted program (on every local device, with an
all-reduce across them when there are several), between host spans of the
kind the drivers write, with an idle gap in the middle. Writes the trace
under ``chiprun_out/<name>/`` so that a chip call brings it back; the file
is then committed under ``tests/benchmark/data``.

  python -m benchmark.tools.record_trace [--name trace_tiny]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--name", default="trace_tiny")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    mesh = Mesh(devs, ("dp",))
    x = jax.device_put(jnp.ones((len(devs) * 256, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P("dp")))
    w = jax.device_put(jnp.ones((1024, 1024), jnp.bfloat16), NamedSharding(mesh, P()))

    @jax.jit
    def step(x, w):
        for _ in range(8):
            x = jnp.tanh(x @ w) * 0.01
        # replicated output of a batch-sharded input: an all-reduce on a mesh
        return jnp.sum(x.astype(jnp.float32), axis=0)

    step(x, w).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", args.name)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    span = jax.profiler.TraceAnnotation
    for i in range(3):
        with span("bench:train"):
            step(x, w).block_until_ready()
        with span("bench:data_next"):
            time.sleep(0.002)
    with span("bench:host_callback"):
        time.sleep(0.01)
    with span("bench:train"):
        step(x, w).block_until_ready()
    jax.profiler.stop_trace()
    print(f"{devs[0].platform} {devs[0].device_kind!r} x{len(devs)} -> {out}")


if __name__ == "__main__":
    main()
