"""The feed's placement: a host batch goes from host memory straight to the
devices that train on it, in one ``device_put`` of its tree, and never
through a single-device array first (which lands every global leaf on the
default device and re-lays it from there behind the running step).

Four of conftest's forced host devices as a dp=4 mesh, as
``tests/test_parallel_exec.py`` builds its own. The learners' placement
methods run on instances that skip ``__init__`` (no model, no compile):
they read only the mesh, the shardings and the feeder's spans.
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distar_tpu.learner import RLLearner, SLLearner
from distar_tpu.learner.lm_learner import LMLearner
from distar_tpu.learner.data import fake_rl_batch, fake_sl_batch
from distar_tpu.obs import feed_spans, get_registry
from distar_tpu.parallel import (
    MeshConfigError,
    MeshSpec,
    assemble_global,
    batch_sharding,
    make_mesh,
)
from distar_tpu.parallel.mesh import time_batch_sharding

B, T = 4, 2


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec(dp=4), jax.devices()[:4])


@pytest.fixture(scope="module")
def shardings(mesh):
    return dict(
        repl=NamedSharding(mesh, P()),
        flat=batch_sharding(mesh, batch_size=B),
        batch=time_batch_sharding(mesh),  # no sp axis here: batch_nosp is never asked for
    )


def _parent_place(x, sharding):
    """The per-leaf form this placement replaced, kept as the reference:
    the leaf made a device array first, then re-laid under its sharding."""
    return jax.device_put(jnp.asarray(x), sharding)


def _learner(cls, mesh, shardings):
    """A learner that has only what its placement reads."""
    self = object.__new__(cls)
    self.mesh = mesh
    self._shardings = shardings
    self._feed_spans = feed_spans(self.name)
    self.cfg = SimpleNamespace(learner={})  # no max_entities: _cap is the identity
    return self


def _lm_batch():
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 1000, (B, 16))  # int64, as a tokenizer's arrays are
    return {"tokens": tokens, "labels": np.roll(tokens, -1, axis=1)}


def _place(kind, mesh, shardings):
    """(the host leaves the learner places, the placed tree)."""
    if kind == "sl":
        host = fake_sl_batch(B, T)
        placed = _learner(SLLearner, mesh, shardings)._place_batch(host)
        assert placed.pop("_on_device") is True
        kept = {k: placed.pop(k) for k in ("new_episodes", "traj_lens")}
        assert all(isinstance(v, np.ndarray) for v in kept.values())
        host = {k: v for k, v in host.items() if k not in kept}
    elif kind == "rl":
        host = fake_rl_batch(B, T, hidden_size=8, hidden_layers=2)
        host.pop("model_last_iter", None)
        placed = _learner(RLLearner, mesh, shardings).shard_batch(host)
    else:
        host = _lm_batch()
        placed = _learner(LMLearner, mesh, shardings)._place_batch(host)
        assert placed.pop("_on_device") is True
    return host, placed


def _reference(kind, host, shardings):
    """What the learners' per-leaf placement gave for ``host``."""
    flat = shardings["flat"]
    if kind == "sl":
        return jax.tree.map(lambda x: _parent_place(x, flat), host)
    if kind == "lm":
        return {k: _parent_place(jnp.asarray(v, jnp.int32), flat) for k, v in host.items()}

    def put(x):
        x = jnp.asarray(x)
        if x.ndim >= 2:
            return _parent_place(x, shardings["batch"])
        if x.ndim == 1 and x.shape[0] % 4 == 0:
            return _parent_place(x, flat)
        return _parent_place(x, shardings["repl"])

    ref = jax.tree.map(put, {k: v for k, v in host.items() if k != "hidden_state"})
    ref["hidden_state"] = jax.tree.map(lambda x: _parent_place(x, flat), host["hidden_state"])
    return ref


def _assert_same_leaves(placed, ref):
    assert jax.tree.structure(placed) == jax.tree.structure(ref)
    got = jax.tree_util.tree_leaves_with_path(placed)
    for (path, a), b in zip(got, jax.tree.leaves(ref)):
        where = jax.tree_util.keystr(path)
        assert isinstance(a, jax.Array), where
        assert a.dtype == b.dtype and a.shape == b.shape, (where, a.dtype, b.dtype)
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim), (where, a.sharding, b.sharding)
        assert a.committed, where
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=where)


def _relaid(token):
    return get_registry().counter("distar_feeder_relaid_leaves_total", token=token).value


@pytest.mark.parametrize("kind", ["sl", "rl", "lm"])
def test_learner_places_its_batch_as_the_per_leaf_form_did(kind, mesh, shardings):
    host, placed = _place(kind, mesh, shardings)
    assert len(jax.tree.leaves(placed)) == len(jax.tree.leaves(host))
    _assert_same_leaves(placed, _reference(kind, host, shardings))
    # the batch mixes every placement the learner has: something is sharded
    assert any(len({s.index for s in x.addressable_shards}) == 4
               for x in jax.tree.leaves(placed))


@pytest.mark.parametrize("kind", ["sl", "rl", "lm"])
def test_no_leaf_goes_through_a_single_device_array(kind, mesh, shardings):
    """Fails where a leaf is made a device array before it is placed: the
    re-lay of a committed single-device array onto the mesh is an explicit
    device-to-device transfer, and a host array's placement is none."""
    token = {"sl": "sllearner", "rl": "rllearner", "lm": "lmlearner"}[kind]
    before = _relaid(token)
    with jax.transfer_guard_device_to_device("disallow_explicit"):
        _, placed = _place(kind, mesh, shardings)
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(placed))
    assert _relaid(token) == before


def test_relaid_counter_counts_device_arrays_in_another_sharding(mesh, shardings):
    token = "relaid-test"
    flat, repl = shardings["flat"], shardings["repl"]
    host = {"a": np.arange(8, dtype=np.float32), "b": np.ones((4, 3), np.int8)}
    assemble_global(host, flat, token=token)
    assert _relaid(token) == 0
    on_one = jax.device_put(host["a"], jax.devices()[0])  # committed, single device
    as_asked = jax.device_put(host["b"], flat)            # already where it goes
    out = assemble_global({"a": on_one, "b": as_asked, "c": host["a"]}, flat, token=token)
    assert _relaid(token) == 1
    assert all(x.sharding.is_equivalent_to(flat, x.ndim) for x in out.values())
    np.testing.assert_array_equal(np.asarray(out["a"]), host["a"])
    assemble_global([on_one, as_asked], [repl, repl], token=token)
    assert _relaid(token) == 3


_RNG = np.random.default_rng(7)
_DTYPES = {
    "int64": lambda s: _RNG.integers(-2**20, 2**20, s),
    "float64": lambda s: _RNG.standard_normal(s),
    "float16": lambda s: _RNG.standard_normal(s).astype(np.float16),
    "uint8": lambda s: _RNG.integers(0, 256, s).astype(np.uint8),
    "int8": lambda s: _RNG.integers(-128, 128, s).astype(np.int8),
    "int16": lambda s: _RNG.integers(-2**15, 2**15, s).astype(np.int16),
    "bool": lambda s: _RNG.integers(0, 2, s).astype(bool),
}
_CANONICAL = {"int64": "int32", "float64": "float32"}


@pytest.mark.parametrize("how", ["flat", "batch", "repl"])
@pytest.mark.parametrize("dtype", list(_DTYPES))
def test_dtype_and_sharding_as_the_per_leaf_form(dtype, how, mesh, shardings):
    sh = shardings[how]
    x = _DTYPES[dtype]((8, 4, 3))
    assert x.dtype == np.dtype(dtype)
    with jax.transfer_guard_device_to_device("disallow_explicit"):
        tree = assemble_global({"x": x, "nested": [x[:4], x[..., 0]]}, sh)
        leaf = assemble_global(x, sh)
    assert leaf.dtype == np.dtype(_CANONICAL.get(dtype, dtype))
    _assert_same_leaves({"x": leaf}, {"x": _parent_place(x, sh)})
    _assert_same_leaves(tree, {"x": _parent_place(x, sh),
                               "nested": [_parent_place(x[:4], sh), _parent_place(x[..., 0], sh)]})
    shards = {s.index for s in leaf.addressable_shards}
    assert len(shards) == (1 if how == "repl" else 4)


def test_tree_of_shardings_is_matched_leaf_by_leaf(mesh, shardings):
    host = {"t": np.arange(24.0).reshape(3, 8), "b": np.arange(8), "s": np.float32(2.5),
            "h": ((np.ones((4, 5)), np.zeros((4, 5))),)}
    want = {"t": shardings["batch"], "b": shardings["flat"], "s": shardings["repl"],
            "h": ((shardings["flat"], shardings["flat"]),)}
    out = assemble_global(host, want)
    _assert_same_leaves(out, jax.tree.map(_parent_place, host, want))
    with pytest.raises(ValueError):
        assemble_global(host, {"t": shardings["batch"]})


def test_indivisible_leaf_in_a_tree_names_shape_dim_and_axes(mesh, shardings):
    host = {"ok": np.zeros((8, 3), np.float32), "bad": np.zeros((2, 6, 5), np.int16)}
    with pytest.raises(MeshConfigError) as e:
        assemble_global(host, {"ok": shardings["flat"], "bad": shardings["batch"]})
    msg = str(e.value)
    assert "array dim 1 of size 6" in msg
    assert "mesh axes ('dp',) (extent 4)" in msg
    assert "(2, 6, 5)" in msg and "cannot shard" in msg


@pytest.mark.parametrize("kind", ["sl", "rl", "lm"])
def test_pod_branch_is_handed_host_arrays(kind, mesh, shardings, monkeypatch):
    """On a pod each process gives ``make_array_from_process_local_data`` its
    own rows: numpy arrays, never a ``jax.Array`` to be copied back first."""
    seen = []
    device_put = jax.device_put

    def from_local(sharding, local):
        seen.append(local)
        return device_put(local, sharding)

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "make_array_from_process_local_data", from_local)
    _, placed = _place(kind, mesh, shardings)
    assert len(seen) == len(jax.tree.leaves(placed)) > 0
    assert all(isinstance(x, np.ndarray) and not isinstance(x, jax.Array) for x in seen)
    assert all(x.sharding.mesh == mesh for x in jax.tree.leaves(placed))
