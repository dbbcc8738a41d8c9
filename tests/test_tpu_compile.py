"""Every Pallas kernel the repo keeps compiles for a DESCRIBED TPU v5e at
flagship shapes, forward and grad, in both compute dtypes; the scatter
connection's way into the spatial encoder compiles without a relayout loop; and
the entity embedding compiles to two products with no row gathered or scattered.

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
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


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


# ------------------------------------------- the scatter connection's layout
# Why `ops/scatter.py` writes add mode as a product and gathers its gradient by
# (y, x, b): its docstring. A change there or in the encoder that brings the
# row-at-a-time scatter, the compiler's relayout loops or the `dp` all-gathers
# back fails here, minutes of compile and a chip run before a trace would show it.
PLANES = 24  # what the spatial encoder concatenates beside the 32-channel map
MAP = (152, 160)


def _scatter_into_conv(emb, loc, planes, w, impl="product"):
    from distar_tpu.ops import scatter_connection

    m = scatter_connection(emb, loc, MAP, "add", impl=impl)
    h = jnp.concatenate([planes, m], axis=-1)
    h = jax.nn.relu(jax.lax.conv_general_dilated(
        h, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    return jnp.sum(h.astype(jnp.float32) ** 2)


def _scatter_into_conv_text(topo, B, dp, impl="product"):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(topo.devices[:dp], ("dp",))
    rows, repl = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    dt = jnp.bfloat16
    args = (
        jax.ShapeDtypeStruct((B * dp, N, D), dt, sharding=rows),
        jax.ShapeDtypeStruct((B * dp, N, 2), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((B * dp, *MAP, PLANES), dt, sharding=rows),
        jax.ShapeDtypeStruct((1, 1, PLANES + D, 32), dt, sharding=repl),
    )
    fn = functools.partial(_scatter_into_conv, impl=impl)
    return jax.jit(jax.value_and_grad(fn, argnums=(0, 3))).lower(*args).compile().as_text()


# an HLO `scatter` instruction (the scope's name is in every op_name)
_SCATTER_OP = re.compile(r" = \S+ scatter\(")


# frames, mesh axis size: 6 x 64 SL frames are three lane tiles of 128; RL's
# (64 + 1) x 6 = 390 and an actor's 16 envs are not, and kept the scatter's
# forward relayout loop until the forward pass became a product (PR 35)
@pytest.mark.parametrize("B,dp", [(384, 1), (384, 4), (390, 1), (16, 1)],
                         ids=("b384", "b384_dp4", "b390", "b16"))
def test_scatter_connection_reaches_the_conv_without_relayout_loops(topo, B, dp):
    text = _scatter_into_conv_text(topo, B, dp)
    # forward: a product on the MXU, no row written one at a time; backward:
    # the rows gathered in place from what the convolution's backward writes
    assert not _SCATTER_OP.search(text)
    assert " gather(" in text and "convolution(" in text
    assert " while(" not in text
    assert f"[{B * MAP[0] * MAP[1]},{D}]" not in text  # the scatter's `[rows, D]` operand
    # the product's left one-hot (c = 4 at D = 32: `ops/scatter.py::_group`) is
    # built inside the product's fusion; no instruction of the program writes it
    entry = text[text.index("\nENTRY "):]
    assert f"[{B},{N},{MAP[0] * MAP[1] // 4}]" not in entry and f"[{B},{N},{MAP[1]},{D}]" not in entry
    if dp > 1:
        # frame b is a batch element of the product and gathers rows of frame
        # b only: nothing but the weight's gradient and the scalar crosses chips
        assert "all-gather" not in text and "collective-permute" not in text
        assert f"[{B * dp * N},{D}]" not in text  # the global batch's entities


def test_the_plain_scatter_is_still_what_impl_xla_compiles(topo):
    """What the benchmark's reference configs name: one `scatter` over the
    map's rows, and (B = 390 is not a multiple of 128) its relayout loop."""
    text = _scatter_into_conv_text(topo, 390, 1, impl="xla")
    assert len(_SCATTER_OP.findall(text)) == 1
    assert text.count(" while(") == 1


@pytest.mark.parametrize("impl,form", [("product", "product"), ("xla", "scatter")])
def test_a_traced_scatter_connection_counts_its_form(impl, form):
    """`distar_scatter_connection_traced_total{form, mode}` moves once a trace
    of the call, under the label of the form the forward pass took."""
    from distar_tpu.obs import get_registry
    from distar_tpu.ops import scatter_connection

    name = "distar_scatter_connection_traced_total"
    read = lambda: {k: v for k, v in get_registry().snapshot().items() if k.startswith(name)}
    before = read()
    kwargs = {} if impl == "product" else {"impl": impl}  # the default is the product
    jax.eval_shape(lambda e, l: scatter_connection(e, l, (5, 6), "add", **kwargs),
                   jax.ShapeDtypeStruct((2, 7, 3), jnp.bfloat16),
                   jax.ShapeDtypeStruct((2, 7, 2), jnp.int32))
    after = read()
    moved = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
    assert moved == {f"{name}{{form={form},mode=add}}": 1}


# ------------------------------------------------------ the entity embedding
# Why `model/encoders.py` multiplies a 997-wide row by one matrix: its module
# docstring. On this chip a gather or a scatter-add over a `[n, 256]` table is a
# `kCustom` fusion that moves one row at a time (2.0 ms at 196,608 rows, 52 of
# them a step until PR 26); a change that brings one back, or that keeps the
# 392 MB row matrix from the forward to the backward pass, fails here.
ROW_MATRIX_BYTES = 384 * N * 997 * 2


def _embedding_with_an_mlp_behind(form, one_chip):
    """Compiled value and gradient of relu(embedding) -> 256-1024-256 MLP at
    6 x 64 frames of 512 entities, bf16: the MLP stands for the transformer
    between the embedding's two passes."""
    from flax import linen as nn
    from distar_tpu.model import default_model_config

    fields = tuple(tuple(f) for f in default_model_config().encoder.entity.fields)

    class Fragment(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = jax.nn.relu(form("ent", fields, x, 256, jnp.bfloat16))
            h = jax.nn.relu(nn.Dense(1024, dtype=jnp.bfloat16)(h))
            return nn.Dense(256, dtype=jnp.bfloat16)(h)

    m = Fragment()
    dtypes = {k: jnp.float32 if arc == "float" else jnp.int32 for k, arc, _ in fields}
    params = jax.eval_shape(
        lambda: m.init(jax.random.PRNGKey(0), {k: jnp.zeros((1, 4), d) for k, d in dtypes.items()}))
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    loss = lambda p, x: jnp.sum(m.apply(p, x).astype(jnp.float32) ** 2)
    return jax.jit(jax.value_and_grad(loss)).lower(
        jax.tree.map(lambda s: on_chip(s.shape, s.dtype), params),
        {k: on_chip((384, N), d) for k, d in dtypes.items()},
    ).compile()


def _row_moves(compiled):
    """`kCustom` fusions with a `[rows, 256]` result: a table's gradient
    (`[n, 256]`) or every entity's row of one (`[196608, 256]`)."""
    return re.findall(r"= \w+\[\d+,256\]\S* fusion\([^\n]*kind=kCustom", compiled.as_text())


def test_entity_embedding_compiles_to_products_that_move_no_rows(one_chip):
    from conftest import gather_and_add_embed
    from distar_tpu.model.encoders import _field_sum_embed

    gathers = _embedding_with_an_mlp_behind(gather_and_add_embed, one_chip)
    product = _embedding_with_an_mlp_behind(_field_sum_embed, one_chip)
    assert len(_row_moves(gathers)) >= 52  # the pattern sees 26 gathers and 26 scatter-adds
    assert not _row_moves(product) and "kind=kCustom" not in product.as_text()
    temp = lambda c: c.memory_analysis().temp_size_in_bytes
    assert temp(product) <= temp(gathers) + 100e6
    # one row matrix at a time, built again for the backward pass: a copy kept
    # through the MLP (XLA merges the two builds unless the backward's is tied to
    # its cotangent) makes 1.39 GB of temporaries, rebuilt 0.61 GB
    assert temp(product) < 2 * ROW_MATRIX_BYTES


# ------------------------------------------------ the token models' kernels
# JAX's own kernels at the published widths of the two token models, as ops/moe.py and
# ops/sequence.py call them (their tilings and block sizes). LFM2-24B-A2B: 8 held experts of
# 2048 x 1536, three matrices, over a buffer of 32,768 rows; 32 heads of 64 over 4 x 8,192
# positions. nemotron_h: 8 held experts of 2688 x 1856, two matrices (neither width a
# multiple of the tiles), over a buffer of 16,384 rows; 32 heads of 128 over 2 x 8,192.
# qwen3_next: 32 held experts of 2048 x 512 over a buffer of 16,384 rows (512 a held expert: the smaller row tile)
EXPERTS = {"lfm2_swiglu": ("swiglu", 32768, 2048, 1536, 8), "nemotron_h_relu2": ("relu2", 16384, 2688, 1856, 8),
           "qwen3_next_swiglu": ("swiglu", 16384, 2048, 512, 32)}


@pytest.mark.parametrize("which", EXPERTS)
@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
def test_grouped_expert_products_compile_for_v5e(one_chip, grad, which):
    from distar_tpu.ops import moe

    # the backend here is the CPU, the target is not: the product is chosen by what is compiled for
    body, rows, d, width, experts = EXPERTS[which]
    fn_body, names = moe.EXPERT_BODIES[body]
    x = jax.ShapeDtypeStruct((rows, d), jnp.bfloat16, sharding=one_chip)
    ws = [jax.ShapeDtypeStruct((experts, width, d) if n == "w2" else (experts, d, width), jnp.bfloat16,
                               sharding=one_chip) for n in names]
    sizes = jax.ShapeDtypeStruct((experts,), jnp.int32, sharding=one_chip)

    def fn(x, sizes, *ws):
        out = fn_body(lambda a, m: moe.grouped_matmul(a, m, sizes), x, *ws)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    n = len(names)
    compiled = jax.jit(jax.grad(fn, argnums=(0, *range(2, 2 + n))) if grad else fn).lower(x, sizes, *ws).compile()
    assert compiled.as_text().count("tpu_custom_call") >= (3 * n if grad else n)


# (B, S, Hkv, G, D) of the four cells whose full-causal core has one head size, the tiles and the backward kernel that
# ``core_plan`` names for them, and what the core's temporaries may hold, forward / with the gradient: the row
# statistics and the fused backward kernel's dQ, once for every key block (a third over what the compiler reads
# today: 0.61 / 1.81, 0.27 / 0.68, 0.27 / 2.30 and 0.13 / 0.95 GB; at 16,384 positions that dQ is [8, 24, 16384, 128]
# bf16, 0.8 GB, where key blocks of 1,024 held 1.6; one head's S x S scores alone would be 1.07 GB more in float32)
CORES = {"lfm2_32x64": ((4, 8192, 8, 4, 64), (1024, 2048, 512, True), (0.8e9, 2.4e9)),
         "nemotron_h_32x128_over_2": ((2, 8192, 2, 16, 128), (1024, 2048, 512, True), (0.4e9, 0.9e9)),
         "qwen3_next_16x256_over_2_by_splash": ((2, 8192, 2, 8, 256), (512, 512, 512, True), (0.4e9, 3.0e9)),
         "laguna_24x128_over_4_at_16k": ((1, 16384, 4, 6, 128), (1024, 2048, 512, True), (0.2e9, 1.3e9))}


@pytest.mark.parametrize("which", CORES)
@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
def test_full_causal_attention_compiles_for_v5e_to_the_kernel_the_rule_names(one_chip, grad, which):
    """The full-causal core of every token cell with one head size compiles for a v5e to the kernel
    ``core_plan`` names for its shape: splash attention at all of them since PR 39 (the narrow heads
    ran the flash kernel until then, a copy of each key/value head a query head with it)."""
    from distar_tpu.ops.sequence import causal_attention, core_plan

    (B, S, Hkv, G, Dh), plan, temp = CORES[which]
    assert core_plan(S, Dh, Dh) == plan
    q = jax.ShapeDtypeStruct((B, S, Hkv, G, Dh), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, Dh), jnp.bfloat16, sharding=one_chip)
    fn = lambda q, k, v: jnp.sum(causal_attention(q, k, v, Dh ** -0.5).astype(jnp.float32) ** 2)
    compiled = jax.jit(jax.grad(fn, argnums=(0, 1, 2)) if grad else fn).lower(q, kv, kv).compile()
    text = compiled.as_text()
    # splash attention and no other kernel: forward, and backward one fused kernel for dQ, dK and dV
    assert "splash_mha" in text and "flash" not in text
    assert text.count("tpu_custom_call") == (2 if grad else 1)
    # no S x S score tensor is held (8.6 GB a sequence in float32 at 8,192 positions)
    assert compiled.memory_analysis().temp_size_in_bytes < temp[grad]


@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
def test_attention_with_a_value_head_of_its_own_compiles_for_v5e_at_8k_positions(one_chip, grad):
    """Latent attention's core at the published head sizes, 16 heads of 192 (score) and 128
    (value) over 2 x 8,192 positions: ONE kernel at those sizes (splash attention), nothing
    padded to reach the flash kernel, which refuses unequal head sizes."""
    from distar_tpu.ops.sequence import causal_attention

    B, S, H = 2, 8192, 16
    q = jax.ShapeDtypeStruct((B, S, H, 1, 192), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((B, S, H, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((B, S, H, 128), jnp.bfloat16, sharding=one_chip)
    fn = lambda q, k, v: jnp.sum(causal_attention(q, k, v, 192 ** -0.5).astype(jnp.float32) ** 2)
    compiled = jax.jit(jax.grad(fn, argnums=(0, 1, 2)) if grad else fn).lower(q, k, v).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "splash" in text
    assert not [ln for ln in text.split("\n") if "pad(" in ln and ("8192,256" in ln or "8192,192]" in ln and ",128]" in ln)]
    # no S x S score tensor is held; the fused backward kernel's unreduced dQ (a float32 copy a key block) is the largest
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
def test_chunked_scan_compiles_for_v5e_and_holds_one_group_of_heads_at_a_time(one_chip, grad):
    """``ops.ssm.chunked_scan`` at nemotron_h's widths over 2 x 8,192 positions: the
    ``128 x 128`` decay matrices of all 64 heads at once are 0.54 GB in float32 and as
    much again for each product and gradient that reads them; a group of 8 at a time,
    recomputed in its backward pass, the whole scan's temporaries stay under a gigabyte."""
    from distar_tpu.ops.ssm import chunked_scan

    b, S, H, P, G, N = 2, 8192, 64, 64, 8, 128
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (spec((b, S, H, P)), spec((b, S, H), jnp.float32), spec((H,), jnp.float32),
            spec((b, S, G, N)), spec((b, S, G, N)))
    fn = lambda *a: jnp.sum(chunked_scan(*a, 128, jnp.bfloat16)[0] ** 2)
    compiled = jax.jit(jax.grad(fn, argnums=(0, 1, 2, 3, 4)) if grad else fn).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
def test_the_mamba2_mixer_compiles_for_v5e_to_the_scans_two_kernels(one_chip, grad):
    """``ops.ssm.Mamba2Mixer`` at nemotron_h's published widths over 2 x 8,192 positions in bfloat16, wrapped as the
    decoder layer wraps it (``jax.checkpoint``): lowered for a TPU its scan is ``mamba2_scan_fwd`` (twice with the
    replay) and ``mamba2_scan_bwd``, no loop over groups of heads or chunks is left under ``ssm_scan``, and no
    chunk's ``128 x 128`` decay matrices of a group's 8 heads are a temporary of the program. What is: the
    projection's ``[2, 8192, 10304]`` results, ``y`` and its cotangent in float32 (268 MB each) and the states the
    grid steps start from (67 MB). And the layer around the kernels stays in the layout they pin: no copy of a float32
    ``[2, 8192, 4096]`` tensor is left (the group norm's reshape to ``[.., 8, 512]`` cost two each way: PERF.md
    section 6, PR 40)."""
    from distar_tpu.ops.ssm import Mamba2Mixer

    mixer = Mamba2Mixer(heads=64, head_dim=64, groups=8, state=128, conv_kernel=4, chunk=128, dtype=jnp.bfloat16)
    u = jax.ShapeDtypeStruct((2, 8192, 2688), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
                          jax.eval_shape(mixer.init, jax.random.PRNGKey(0), u))
    layer = jax.checkpoint(lambda p, u: mixer.apply(p, u))
    fn = lambda p, u: (lambda out, rms: jnp.sum(out.astype(jnp.float32) ** 2) + rms)(*layer(p, u))
    compiled = jax.jit(jax.value_and_grad(fn, argnums=(0, 1)) if grad else fn).lower(params, u).compile()
    text = compiled.as_text()
    calls = [ln.split("=")[0].strip() for ln in text.split("\n") if 'custom_call_target="tpu_custom_call"' in ln]
    assert sorted(name.split(".")[0] for name in calls) == (
        ["%mamba2_scan_bwd", "%mamba2_scan_fwd", "%mamba2_scan_fwd"] if grad else ["%mamba2_scan_fwd"])
    under_scan = [ln for ln in text.split("\n") if "ssm_scan" in ln]
    assert under_scan and not [ln for ln in under_scan if "while(" in ln]
    assert "while(" not in text and not re.search(r"f32\[[0-9,]*128,128,8\]", text)
    assert not re.search(r"= f32\[(2,8192,4096|2048,8,8,512)\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < (3.0e9 if grad else 1.2e9)   # 2.50 and 0.87 GB (PR 40)


@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
def test_chunked_delta_rule_compiles_for_v5e_and_holds_one_group_of_heads_at_a_time(one_chip, grad):
    """``ops.delta.chunked_delta_rule`` at qwen3_next's widths over 2 x 8,192 positions, lowered for a TPU, is its
    Pallas kernels (one forward; one more backward, and the forward once again for every chunk's starting state):
    no loop over groups of heads and no triangular solve is left in the program, and nothing of a chunk's
    ``64 x 64`` system is a temporary of it. What is: the float32 output of all heads (268 MB), and backward its
    cotangent and the starting states (537 MB): 0.47 and 1.07 GB where the XLA form, a group of heads at a time,
    stayed under three."""
    from distar_tpu.ops.delta import chunked_delta_rule

    b, S, Hk, H, K, V = 2, 8192, 16, 32, 128, 128
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (spec((b, S, Hk, K)), spec((b, S, Hk, K)), spec((b, S, H, V)), spec((b, S, H), jnp.float32),
            spec((b, S, H), jnp.float32))
    fn = lambda *a: jnp.sum(chunked_delta_rule(*a, 64, jnp.bfloat16)[0] ** 2)
    compiled = jax.jit(jax.grad(fn, argnums=(0, 1, 2, 3, 4)) if grad else fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (2 if grad else 1)
    assert "while(" not in text and "triangular-solve" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1.3e9 if grad else 0.6e9)


@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
def test_banded_attention_compiles_for_v5e_at_16k_positions_and_holds_no_dq_a_key_block(one_chip, grad):
    """``causal_attention`` with a window at ``laguna``'s sliding layer (36 query heads of 128 over
    4 key/value heads, one sequence of 16,384, window 512): splash attention under a local mask,
    with a dQ kernel of its own. The fused backward kernel writes dQ once for every key block of
    the sequence, 4.8 GB here whatever the mask; with its own kernel the temporaries of the three
    passes stay under a gigabyte and a half."""
    from distar_tpu.ops.sequence import causal_attention

    q = jax.ShapeDtypeStruct((1, 16384, 4, 9, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one_chip)
    fn = lambda q, k, v: jnp.sum(causal_attention(q, k, v, 128 ** -0.5, window=512).astype(jnp.float32) ** 2)
    compiled = jax.jit(jax.grad(fn, argnums=(0, 1, 2)) if grad else fn).lower(q, kv, kv).compile()
    text = compiled.as_text()
    assert "splash" in text and text.count("tpu_custom_call") >= (3 if grad else 1)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# (window, the tiles and backward kernel ``core_plan`` names, what the core's temporaries may hold forward / with the
# gradient) of ``phi4flash``'s differential attention core: 20 key heads of 64, two query heads each, the pair's
# 128-wide value as the value head, over 2 x 8,192 positions
DIFF_CORES = {"full_and_cross": (None, (1024, 2048, 512, True), (0.5e9, 1.4e9)),       # 0.34 / 1.09 GB (PR 42)
              "window_512": (512, (512, 512, 512, False), (0.5e9, 1.3e9))}                 # 0.34 / 1.01 GB


@pytest.mark.parametrize("which", DIFF_CORES)
@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
def test_differential_attention_core_compiles_for_v5e_at_score_64_value_128(one_chip, grad, which):
    """``causal_attention`` as ``ops.sequence.DifferentialAttention`` calls it, ONE call a layer: splash attention at
    the tiles ``core_plan`` names for score heads of 64 with value heads of 128, under the causal mask (the full and
    the cross layer) and under the band of 512 (a dQ kernel of its own there: the fused kernel's dQ copies are the
    whole sequence's whatever the mask)."""
    from distar_tpu.ops.sequence import causal_attention, core_plan

    window, plan, temp = DIFF_CORES[which]
    assert core_plan(8192, 64, 128, window) == plan
    q = jax.ShapeDtypeStruct((2, 8192, 20, 2, 64), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((2, 8192, 20, 64), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 8192, 20, 128), jnp.bfloat16, sharding=one_chip)
    fn = lambda q, k, v: jnp.sum(causal_attention(q, k, v, 0.125, window=window).astype(jnp.float32) ** 2)
    compiled = jax.jit(jax.grad(fn, argnums=(0, 1, 2)) if grad else fn).lower(q, k, v).compile()
    text = compiled.as_text()
    assert "splash" in text and text.count("tpu_custom_call") == ((2 if plan[3] else 3) if grad else 1)
    assert compiled.memory_analysis().temp_size_in_bytes < temp[grad]


@pytest.mark.parametrize("grad", (False, True), ids=("fwd", "grad"))
def test_the_mamba1_mixer_compiles_for_v5e_to_the_selective_scans_two_kernels(one_chip, grad):
    """``ops.ssm.Mamba1Mixer`` at ``phi4flash``'s published widths (2560 -> 5120 channels, a state of 16) over 2 x
    8,192 positions in bfloat16, wrapped as the decoder layer wraps it (``jax.checkpoint``): lowered for a TPU its scan
    is ``mamba1_scan_fwd`` (twice with the replay) and ``mamba1_scan_bwd``, no loop over positions or chunks is left
    in the program, and nothing of the states' history (``[2, 8192, 5120, 16]`` float32, 5.4 GB) is a temporary of
    it: the largest array with the state's two axes is the states the grid steps start from, one a 128 positions
    (``[2, 64, 16, 5120]``, 42 MB)."""
    from distar_tpu.ops.ssm import Mamba1Mixer

    mixer = Mamba1Mixer(inner=5120, state=16, dt_rank=160, dtype=jnp.bfloat16)
    u = jax.ShapeDtypeStruct((2, 8192, 2560), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
                          jax.eval_shape(mixer.init, jax.random.PRNGKey(0), u))
    layer = jax.checkpoint(lambda p, u: mixer.apply(p, u))
    fn = lambda p, u: (lambda out, memory, rms: jnp.sum(out.astype(jnp.float32) ** 2)
                       + jnp.sum(memory.astype(jnp.float32)) + rms)(*layer(p, u))
    compiled = jax.jit(jax.value_and_grad(fn, argnums=(0, 1)) if grad else fn).lower(params, u).compile()
    text = compiled.as_text()
    calls = [ln.split("=")[0].strip() for ln in text.split("\n") if 'custom_call_target="tpu_custom_call"' in ln]
    assert sorted(name.split(".")[0] for name in calls) == (
        ["%mamba1_scan_bwd", "%mamba1_scan_fwd", "%mamba1_scan_fwd"] if grad else ["%mamba1_scan_fwd"])
    assert "while(" not in text and [ln for ln in text.split("\n") if "mamba1_scan" in ln]
    history = [int(n) for n in re.findall(r"f32\[2,(\d+),16,5120\]", text)] + [
        int(n) for n in re.findall(r"f32\[2,(\d+),5120,16\]", text)]
    assert max(history, default=0) <= 8192 // 128
    assert compiled.memory_analysis().temp_size_in_bytes < (3.8e9 if grad else 1.8e9)   # 3.03 and 1.34 GB (PR 42)


def _forward_kernels(text):
    """The names of splash attention's forward kernels among a compiled text's custom calls."""
    return re.findall(r"^\s*(?:ROOT )?%(splash_mha_fwd[\w.]*) = .*custom-call\(", text, flags=re.M)


# the attention module of one decoder layer at its published sizes, the positions of its cell, and the forward
# kernels in the gradient's text: a full-causal core's forward results are kept by the layer's remat
# (``ops.sequence.CORE_KEPT``), a banded core is replayed with the layer
LAYERS_UNDER_REMAT = {
    "kimi_vl_latent_full": (lambda s: s.LatentAttention(16, 512, 128, 64, 128, dtype=jnp.bfloat16), (2, 8192, 2048), 1),
    "laguna_sliding_window_512": (
        lambda s: s.CausalGQAttention(36, 4, 128, dtype=jnp.bfloat16, window=512), (1, 16384, 2048), 2),
}


def _layer_text(one_chip, make, B, S, d):
    """One attention layer through ``model/token_decoder.py::decode`` with ``remat: true``, value and gradient,
    compiled for a v5e: the compiled text."""
    from flax import linen as nn

    from distar_tpu.model.token_decoder import decode
    from distar_tpu.ops import sequence

    class Layer(nn.Module):
        cfg: dict
        index: int

        @nn.compact
        def __call__(self, x):
            return x + make(sequence)(sequence.RMSNorm(1e-5, name="norm")(x)), {}

    class Model(nn.Module):
        cfg: dict

        @nn.compact
        def __call__(self, tokens):
            return decode(self, tokens, Layer, 1, eps=1e-5, stacked=(), tied=True)

    model = Model({"vocab_size": 256, "hidden_size": d, "dtype": "bfloat16", "remat": True})
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens))
    fn = lambda p, t: jnp.sum(model.apply(p, t)[0] ** 2)
    return jax.jit(jax.value_and_grad(fn)).lower(params, tokens).compile().as_text()


@pytest.mark.parametrize("which", LAYERS_UNDER_REMAT)
def test_a_layers_remat_runs_a_full_causal_cores_forward_kernel_once_on_a_v5e(one_chip, which):
    """The text holds ONE ``splash_mha_fwd`` custom call for latent attention's core over the whole triangle (two
    until PR 44: the replay ran it again for ``out`` and ``logsumexp``) and two for the band."""
    make, (B, S, d), runs = LAYERS_UNDER_REMAT[which]
    assert len(_forward_kernels(_layer_text(one_chip, make, B, S, d))) == runs


# ``laguna``'s two attention layers at their published sizes over 16,384 positions: query heads held here, the
# rotation's width, YaRN's keys
QK_PREPARATIONS = {"laguna_sliding_36_heads_whole_head_turned": (36, "sliding_attention", dict(window=512)),
                   "laguna_full_24_heads_yarn_over_the_first_64": (24, "full_attention", {})}


@pytest.mark.parametrize("which", QK_PREPARATIONS)
def test_a_laguna_layers_qk_preparation_compiles_for_v5e_to_one_pass_each_way(one_chip, which):
    """``CausalGQAttention`` with a head of 128 through ``decode`` with ``remat: true``, value and gradient: from
    ``q_proj``'s and ``k_proj``'s results to the core's operands the text holds ``head_turn_fwd`` four times (q and k,
    the forward pass and the replay) and ``head_turn_bwd`` twice, and under ``attn_proj`` NO instruction of the ENTRY
    computation writes a float32 tensor of the heads' size (``f32[1,16384,heads,128]`` or its halves: until PR 45 the
    normed head and the two halves of ``rotate_half`` went to memory in float32, three times a step), nor copies the
    projection's result into another layout on its way into the kernel."""
    from distar_tpu.model import default_laguna_config, laguna

    heads, kind, more = QK_PREPARATIONS[which]
    turn = default_laguna_config()["rope_parameters"][kind]
    more = dict(more, rope_theta=turn["rope_theta"], rotary_dim=int(128 * turn["partial_rotary_factor"]),
                yarn={k: turn[k] for k in laguna.YARN_KEYS} if turn["rope_type"] == "yarn" else None)
    text = _layer_text(one_chip, lambda s: s.CausalGQAttention(heads, 4, 128, eps=1e-6, dtype=jnp.bfloat16, **more), 1, 16384, 3072)
    calls = re.findall(r"^\s*(?:ROOT )?%(head_turn_[a-z]+)[.\d]* = .*custom-call\(", text, flags=re.M)
    assert sorted(calls) == ["head_turn_bwd"] * 2 + ["head_turn_fwd"] * 4
    # the ENTRY computation's instructions under the scope: (name, result type, opcode)
    under = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(", ln).groups()
             for ln in text[text.index("\nENTRY "):].split("\n") if "/attn_proj/" in ln and " = " in ln]
    assert len(under) > 20 and {"q_proj", "k_proj", "o_proj"} <= set(re.findall(r"/attn_proj/(\w+)/dot_general", text))
    wide = [name for name, result, _ in under if re.search(rf"f32\[(1,)?16384,({heads}|4),(128|64)\]", result)]
    assert not wide, wide
    copied = [name for name, result, opcode in under if opcode == "copy" and re.search(rf"bf16\[1,16384,({heads * 128}|512)\]", result)]
    assert not copied, copied


# ``tools/tpu_compile_check.py --what``: the forward kernels of the whole step's text (one a full-causal core, two
# a banded one: laguna 2 + 2 x 3, phi4flash 2 + 2 x 1)
TOKEN_PROGRAMS = {"lm": 1, "nh": 1, "kimi": 6, "qwen": 1, "laguna": 8, "phi4flash": 4}
REMAT_STARTS_AT = 15.27e9    # ``total_bytes`` above which the compiler rematerialises on its own (PERF.md 7.19 (e))


@pytest.mark.slow
@pytest.mark.parametrize("what", TOKEN_PROGRAMS)
def test_a_token_step_holds_one_forward_kernel_a_full_causal_core_and_fits_the_chip(topo, what):
    """The whole train step of a published-width file, as ``tools/tpu_compile_check.py`` compiles it (1-5 minutes
    each, so not tier-1): ``kimi``'s text holds six ``splash_mha_fwd`` custom calls, not twelve, and every
    program's ``total_bytes`` stays under what makes the compiler rematerialise."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "tools"))
    import tpu_compile_check as check

    from distar_tpu.utils import read_config

    compiled = check.check_lm(topo, read_config(check.LM_CONFIGS[what]), 0, "")
    assert len(_forward_kernels(compiled.as_text())) == TOKEN_PROGRAMS[what]
    mem = compiled.memory_analysis()     # donated arguments are aliased to outputs: counted once
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes) < REMAT_STARTS_AT
