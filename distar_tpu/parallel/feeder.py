"""Sharded batch feeding: host batches -> global device arrays on the mesh.

Role of the reference's rank-local DataLoader + async CUDA copy (reference:
distar/agent/default/rl_training/rl_dataloader.py:45-167 — each NCCL rank
pulls its own batch and copies it to its own GPU): under GSPMD there is ONE
logical batch sharded over the mesh, so the feeder owns the two halves of
that contract:

* ``assemble_global`` — turn a process-local host batch (a tree of numpy
  arrays, or one) into global ``jax.Array``s under the requested
  ``NamedSharding``s. Single-process runs (one host owns every mesh
  device) make ONE ``jax.device_put(tree, shardings)`` of the HOST arrays:
  the runtime slices each leaf on the host (a view where the batch axis
  leads) and streams every shard from host memory to the chip that trains
  on it, asynchronously. The leaves must not be turned into device arrays
  first (``jnp.asarray``): that lands the whole global leaf on the default
  device, where re-laying it is a slicing program queued behind the running
  step and device-to-device copies to the other chips. A leaf that does
  arrive as a ``jax.Array`` in another sharding is still re-laid, and
  counted in ``distar_feeder_relaid_leaves_total{token}`` (0 when the feed
  hands over host arrays, as the learners do). Multi-process runs (a pod:
  each host's dataloader pulled only its own batch shard) go through
  ``jax.make_array_from_process_local_data`` leaf by leaf, which assembles
  the global array from per-host locals without ever materialising the
  full batch on any single host.

* ``ShardFeeder`` — the double-buffer: a background thread pulls the next
  host batch from the dataloader (collate happens there), places it via
  ``place_fn`` (the learner's sharding-aware placement), and banks up to
  ``depth`` placed batches. Because ``device_put``/``make_array...`` are
  asynchronous, the H2D transfer of batch N+1 overlaps the device step of
  batch N; the learner's ``next()`` only waits when the host side cannot
  keep up — and that wait is the headline starvation metric
  (``distar_feeder_wait_seconds``; the smoke contract is feeder wait <
  device step time).
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Sharding

from ..obs import feed_spans, get_registry
from .mesh import MeshConfigError

_SENTINEL = object()


def assemble_global(tree, shardings, token: str = "feeder"):
    """Host batch -> global device arrays: ``tree`` is one array or a tree
    of them, ``shardings`` one ``NamedSharding`` for every leaf or a tree
    of them shaped like ``tree``.

    Every shard goes from host memory to its own device in one
    ``jax.device_put`` of the whole tree. Raises ``MeshConfigError`` when a
    sharded dimension doesn't divide its mesh extent — at the call site
    with shapes in the message, instead of an opaque XLA error from inside
    the jitted step. Leaves that arrive as device arrays in another
    sharding are re-laid by the runtime (through the device they sit on)
    and counted in ``distar_feeder_relaid_leaves_total{token}``.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if isinstance(shardings, Sharding):
        shardings = [shardings] * len(leaves)
    else:
        shardings = treedef.flatten_up_to(shardings)
    leaves = [x if hasattr(x, "dtype") else np.asarray(x) for x in leaves]
    relaid = 0
    for x, sh in zip(leaves, shardings):
        _check_divisible(x, sh)
        relaid += isinstance(x, jax.Array) and not x.sharding.is_equivalent_to(sh, x.ndim)
    get_registry().counter(
        "distar_feeder_relaid_leaves_total",
        "batch leaves that reached placement as device arrays in another "
        "sharding and were re-laid through the device holding them",
        token=token,
    ).inc(relaid)
    if jax.process_count() == 1:
        placed = jax.device_put(leaves, shardings)
    else:
        # pod path: each leaf is this host's batch shard; every process
        # contributes its local rows and jax glues them into one global Array
        placed = [jax.make_array_from_process_local_data(sh, np.asarray(x))
                  for x, sh in zip(leaves, shardings)]
    return treedef.unflatten(placed)


def _check_divisible(x, sharding) -> None:
    spec = getattr(sharding, "spec", None)
    mesh = getattr(sharding, "mesh", None)
    if spec is None or mesh is None:
        return
    shape = getattr(x, "shape", ())
    for dim, names in enumerate(spec):
        if names is None or dim >= len(shape):
            continue
        names = names if isinstance(names, tuple) else (names,)
        extent = 1
        for n in names:
            extent *= mesh.shape[n]
        if extent > 1 and shape[dim] % extent:
            raise MeshConfigError(
                f"array dim {dim} of size {shape[dim]} does not divide the "
                f"mesh axes {names} (extent {extent}); global shape {shape} "
                f"cannot shard as {spec}"
            )


class ShardFeeder:
    """Wraps a host-batch iterator; yields placed (sharded) batches.

    The learners' one prefetcher: a double buffer (bounded queue, error
    propagation through ``__next__``, sentinel shutdown) with the
    mesh-aware placement contract and the ``distar_feeder_*``
    instrumentation. ``place_fn`` receives the
    raw host batch and returns the device-placed batch — for learners that
    is ``_place_batch`` (entity cap, then one ``assemble_global`` of the
    batch's host tree).

    The producer thread's phases go through ``obs.feed_spans(token)``
    (``distar:feed/<phase>`` in a profiler trace, ``distar_feeder_phase_
    seconds{phase,token}``): ``pull`` and ``put_wait`` here, ``cap`` and
    ``put`` inside the learner's ``place_fn``, under the same token.
    """

    def __init__(self, dataloader, place_fn: Callable, depth: int = 2,
                 token: str = "feeder"):
        if depth < 1:
            raise ValueError(f"ShardFeeder depth must be >= 1, got {depth}")
        self._it = iter(dataloader)
        self._place = place_fn
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._depth = depth
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        # host-side running totals for cheap in-process assertions/reports
        # (the registry histograms carry the cross-process view)
        self.batches = 0
        self.total_wait_s = 0.0
        self.total_place_s = 0.0
        reg = get_registry()
        self._m_batches = reg.counter(
            "distar_feeder_batches_total",
            "host batches placed onto the mesh", token=token,
        )
        self._m_wait = reg.histogram(
            "distar_feeder_wait_seconds",
            "consumer-side starvation: wall-clock next() blocked on the feeder",
            token=token,
        )
        self._m_place = reg.histogram(
            "distar_feeder_place_seconds",
            "host pull + collate + device placement time per batch",
            token=token,
        )
        self._m_leaves = reg.histogram(
            "distar_feeder_batch_leaves",
            "device arrays in a placed batch (one device_put places them all)", token=token,
        )
        self._spans = feed_spans(token)
        self._m_occ = reg.gauge(
            "distar_feeder_occupancy",
            "placed-batch share of the double buffer (0..1)", token=token,
        )
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="shard-feeder"
        )
        self._thread.start()

    # ------------------------------------------------------------- producer
    def _loop(self) -> None:
        spans = self._spans
        try:
            while not self._stop.is_set():
                t0 = time.monotonic()
                try:
                    with spans.span("pull"):
                        batch = next(self._it)
                except StopIteration:
                    return
                placed = self._place(batch)
                dt = time.monotonic() - t0
                self.total_place_s += dt
                self._m_place.observe(dt)
                self._m_leaves.observe(
                    sum(isinstance(x, jax.Array) for x in jax.tree.leaves(placed)))
                with spans.span("put_wait"):
                    while not self._stop.is_set():
                        try:
                            self._q.put(placed, timeout=0.2)
                            break
                        except queue.Full:
                            continue
        except BaseException as e:  # surfaced on the consumer side
            self._err = e
        finally:
            self._q.put(_SENTINEL)

    # ------------------------------------------------------------- consumer
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        t0 = time.monotonic()
        item = self._q.get()
        waited = time.monotonic() - t0
        if item is _SENTINEL:
            if self._err is not None:
                raise self._err
            raise StopIteration
        self.batches += 1
        self.total_wait_s += waited
        self._m_batches.inc()
        self._m_wait.observe(waited)
        self._m_occ.set(self._q.qsize() / self._depth)
        return item

    def occupancy(self) -> float:
        return self._q.qsize() / self._depth

    def stats(self) -> dict:
        """Host-side totals for smoke assertions (the prefetch-overlap
        contract: the feeder is ahead of the steps, so placed batches wait
        in the buffer and mean wait << mean step time)."""
        n = max(self.batches, 1)
        return {
            "batches": self.batches,
            "occupancy": self.occupancy(),
            "wait_s_mean": self.total_wait_s / n,
            "place_s_mean": self.total_place_s / n,
            "wait_s_total": self.total_wait_s,
        }

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        # reap the producer: close() returning while it may still be mid
        # pull/collate/place races learner teardown (it would touch freed
        # device state); the drain above unblocked any pending put. Short
        # bound: a producer blocked in next(self._it) can't be interrupted
        # — waiting longer buys nothing (it dies with the process as before)
        self._thread.join(timeout=0.5)
