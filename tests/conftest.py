"""Test harness: force an 8-device virtual CPU platform.

This is the TPU analogue of the reference's FakeLink fake distributed backend
(distar/ctools/utils/fake_linklink.py) — multi-device collective code paths
run single-process on virtual devices. The platform is pinned to the CPU
here whatever the environment says: the real-TPU path is exercised by
``chip_smoke.py``, not by tests, and a test run must never hold the chip.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# implicit request-span minting OFF suite-wide (the DISTAR_PERF_AOT=0
# precedent): hundreds of serve/replay tests would otherwise each pay the
# tracing hot path for zero test value on a 1-core CI host. Explicit
# ``start_trace``/``finish_trace`` calls (the PR 1 trajectory pipeline)
# are unaffected; tracing tests opt back in via ``obs.set_tracing(True)``
# (tests/test_trace_fleet.py) and its subprocesses via DISTAR_TRACE=1.
# Must be set BEFORE distar_tpu.obs imports (the flag is read at import).
os.environ.setdefault("DISTAR_TRACE", "0")

import jax

jax.config.update("jax_platforms", "cpu")
# persistent compile cache: identical small-model jits recur across test
# modules; cached XLA executables cut warm suite time drastically
# (placement and the host-keyed CPU sub-directory: utils/compile_cache.py)
from distar_tpu.utils.compile_cache import configure as _configure_cache  # noqa: E402

_configure_cache()

import numpy as np
import pytest

# --------------------------------------------------------------- lockwatch
# DISTAR_LOCKWATCH=1: wrap threading.Lock/RLock creation (distar_tpu code
# only) + blocking primitives for the whole session, then report the
# per-thread lock-order graph (ABBA inversions) and held-while-blocking
# pairs at session end — the dynamic witness for the static lock rules
# (docs/analysis.md). Must install BEFORE distar_tpu modules construct
# their locks, i.e. at conftest import.
_LOCKWATCH = os.environ.get("DISTAR_LOCKWATCH") == "1"
if _LOCKWATCH:
    from distar_tpu.analysis import lockwatch as _lockwatch

    _lockwatch.install()


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if not _LOCKWATCH:
        return
    rep = _lockwatch.report()
    baseline = _lockwatch.load_baseline(
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "tools", "lockwatch_baseline.json"))
    bad = _lockwatch.unbaselined(rep, baseline)
    out = os.environ.get("DISTAR_LOCKWATCH_OUT")
    if out:
        import json as _json

        with open(out, "w") as f:
            _json.dump({"report": rep, "unbaselined": bad}, f, indent=1)
    terminalreporter.section("lockwatch")
    terminalreporter.write_line(_lockwatch.render_report(rep, bad))


@pytest.fixture(autouse=True, scope="session")
def _scoped_experiments_root(tmp_path_factory):
    """Scope every default experiment dir to a fresh tmp root.

    Learners resolve ``experiments/<name>`` relative to
    ``DISTAR_EXPERIMENTS_ROOT`` (base_learner.experiments_root). Without
    this, a test that doesn't pass ``save_path`` writes checkpoints into
    the repo's ``experiments/`` — and a LATER run's auto-resume silently
    restores that stale state (the PR 5 tier-1 poisoning: sl_train resumed
    a previous invocation's checkpoint and ran 0 fresh iterations).
    Subprocesses spawned by tests inherit the env var, so CLI-level tests
    are scoped too."""
    root = tmp_path_factory.mktemp("experiments")
    prev = os.environ.get("DISTAR_EXPERIMENTS_ROOT")
    os.environ["DISTAR_EXPERIMENTS_ROOT"] = str(root)
    yield
    if prev is None:
        os.environ.pop("DISTAR_EXPERIMENTS_ROOT", None)
    else:
        os.environ["DISTAR_EXPERIMENTS_ROOT"] = prev


@pytest.fixture(autouse=True, scope="session")
def _no_background_perf_aot():
    """Disable the perf monitor's background AOT flop extraction suite-wide.

    Every learner that trains would otherwise spawn one background
    lower()/cost_analysis() thread (obs/perf.py) — dozens of concurrent
    re-traces of small models on an oversubscribed CPU host slow the suite
    for zero test value. Tests that exercise the AOT path opt back in per
    learner via ``learner.perf.aot=True``."""
    prev = os.environ.get("DISTAR_PERF_AOT")
    os.environ["DISTAR_PERF_AOT"] = "0"
    yield
    if prev is None:
        os.environ.pop("DISTAR_PERF_AOT", None)
    else:
        os.environ["DISTAR_PERF_AOT"] = prev


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def chaos():
    """Seeded fault injector (distar_tpu/resilience/chaos.py); any patches it
    installed are restored on teardown so faults never leak across tests."""
    from distar_tpu.resilience.chaos import ChaosInjector

    inj = ChaosInjector(seed=0)
    yield inj
    inj.restore()


@pytest.fixture(autouse=True, scope="module")
def _bound_compiled_program_accumulation():
    """Drop compiled-executable caches at each module boundary.

    A single long-lived process accumulating a few hundred XLA-CPU
    executables segfaulted inside backend_compile_and_load (deterministic
    at the same test, twice, near the end of a serial full-suite run).
    Clearing per-module bounds native accumulation; the persistent disk
    cache keeps cross-module recompiles cheap."""
    yield
    jax.clear_caches()


@pytest.fixture
def executables_dropped():
    """For the tests that run a Pallas kernel interpreted (``tests/test_qwen3_next.py``, ``tests/test_nemotron_h.py``):
    such kernels are long programs, and every one XLA:CPU loads holds a few thousand memory mappings until its ``jit``
    is dropped. A dozen of them took a test file's process to the kernel's limit of 65,530 (``vm.max_map_count``) and a
    later test's compile died of it (PERF.md section 7.29 (e))."""
    yield
    jax.clear_caches()


# shared tiny flagship-shaped model config for learner/actor tests (several
# older test files still carry local copies; new tests should import this)
SMALL_MODEL = {
    "encoder": {
        "entity": {"layer_num": 1, "hidden_dim": 32, "output_dim": 16, "head_dim": 8},
        "spatial": {"down_channels": [4, 4, 8], "project_dim": 4, "resblock_num": 1, "fc_dim": 16},
        "scatter": {"output_dim": 4},
        "core_lstm": {"hidden_size": 32, "num_layers": 1},
    },
    "policy": {
        "action_type_head": {"res_dim": 16, "res_num": 1, "gate_dim": 32},
        "delay_head": {"decode_dim": 16},
        "queued_head": {"decode_dim": 16},
        "selected_units_head": {"func_dim": 16},
        "target_unit_head": {"func_dim": 16},
        "location_head": {"res_dim": 8, "res_num": 1, "upsample_dims": [4, 4, 1], "map_skip_dim": 8},
    },
    "value": {"res_dim": 8, "res_num": 1},
}


def gather_and_add_embed(mdl_prefix, fields, x, width, dtype):
    """The entity encoder's field embedding as a table a field, a gather each
    and their sum: the form ``model/encoders.py::_field_sum_embed`` had until
    PR 26, kept as the plain reference of the one-product form (value, every
    leaf's gradient, the parameter tree and its seeded values). Call it inside
    an ``nn.compact`` method, as the encoder calls its own."""
    import jax.numpy as jnp
    from flax import linen as nn

    from distar_tpu.ops import binary_encode

    total = None
    for key, arc, n in fields:
        v, name = x[key], f"{mdl_prefix}_{key}"
        if arc == "one_hot":
            emb = nn.Embed(n, width, dtype=dtype, name=name)(
                jnp.clip(v.astype(jnp.int32), 0, n - 1))
        elif arc == "binary":
            emb = nn.Dense(width, use_bias=False, dtype=dtype, name=name)(binary_encode(v, n))
        elif arc == "float":
            emb = nn.Dense(width, use_bias=False, dtype=dtype, name=name)(
                v.astype(jnp.float32)[..., None])
        else:
            raise NotImplementedError(arc)
        total = emb if total is None else total + emb
    return total
