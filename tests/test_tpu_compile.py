"""Every Pallas kernel the repo keeps compiles for a DESCRIBED TPU v5e at
flagship shapes, forward and grad, in both compute dtypes.

No chip is attached: the TPU compiler installed in this image compiles for a
topology description and raises what the chip's compiler would raise (a
block that breaks the tiling rule, too much VMEM). Interpret-mode tests
cannot see any of that. Nothing runs, so these say nothing about results or
speed. The whole-step compiles take minutes and live in
``tools/tpu_compile_check.py``, not here.
"""
import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest

from distar_tpu.ops import pallas_kernels as pk

# flagship geometry: 2 heads x 512 entities x head_dim 128; 512 entities x 32
# channels scattered onto the 152x160 map; B*T = 6 x 64 is the learner's
# flattened batch, 8 an actor's env batch
H, N, DH, D, HW = 2, 512, 128, 32, 152 * 160
BATCHES = (8, 384)
DTYPES = (jnp.bfloat16, jnp.float32)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    # a described-device compile is written to the persistent cache but can
    # never be read back without the chip (it warns and recompiles): off
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _attention(one_chip, B, dtype):
    qkv = jax.ShapeDtypeStruct((B, H, N, DH), dtype, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((B, N), jnp.bool_, sharding=one_chip)
    # interpret=False: the backend here is the CPU, the target is not
    fn = lambda q, k, v, m: pk.masked_attention(q, k, v, m, False)
    return fn, (qkv, qkv, qkv, mask), (0, 1, 2)


def _scatter(one_chip, B, dtype):
    emb = jax.ShapeDtypeStruct((B, N, D), dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((B, N), jnp.int32, sharding=one_chip)
    fn = lambda e, i: pk.scatter_add_onehot(e, i, HW, False)
    return fn, (emb, idx), (0,)


KERNELS = {"masked_attention": _attention, "scatter_add_onehot": _scatter}


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("B", BATCHES)
@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, grad, B, dtype):
    fn, args, argnums = KERNELS[kernel](one_chip, B, dtype)
    if grad:
        fwd = fn
        fn = jax.grad(lambda *a: jnp.sum(fwd(*a).astype(jnp.float32) ** 2),
                      argnums=argnums)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel itself, native


def test_every_kernel_in_the_module_is_covered():
    """A pallas_call added to ops/pallas_kernels.py without a compile case
    here fails: one forward kernel per public entry point listed above."""
    import inspect

    src = inspect.getsource(pk)
    assert src.count("pl.pallas_call(") == len(KERNELS)
    entry_points = set(re.findall(r"^def ([a-z]\w*)\(", src, flags=re.M))
    for name in KERNELS:
        assert name in entry_points
