"""Pallas-vs-XLA kernel check and microbench for the two flagship kernels
(SURVEY.md §2.3 scatter_connection, §5 entity masked attention), and for the
token model's gated delta rule (``ops/delta.py``: one Gated DeltaNet layer's
rule at ``qwen3_next_train_b2s8k``'s shape under ``jax.checkpoint``, its two
Pallas kernels beside the XLA form: PR 37's fragment; ``ops/ssm.py``: one
Mamba-2 layer's scan at ``nemotron_twotower_train_b2s8k``'s shape, the same
way: PR 40's; ``ops/head_turn.py``: one attention layer's q/k preparation at
``laguna_train_b1s16k``'s two shapes, the same way: PR 45's). ``--ops`` names
the fragments to run (all of them by default).

Runs each kernel at actor-inference and learner-training shapes, in bf16 and
f32, forward and forward+backward, against its jnp reference: first a
correctness comparison (fails on a mismatch), then a timing of both. On a
TPU the Pallas kernels lower natively; anywhere else they run in interpret
mode at toy shapes (labelled — interpret numbers are for correctness only,
never perf), and the last line counts how many ``pallas_call``s fell back.

Usage:
  python tools/bench_kernels.py [--platform auto|tpu|cpu] [--out artifacts/...json]

``chip_smoke.py`` runs this with ``--platform tpu`` as its kernel phase.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _time(fn, args, iters=30, warmup=3):
    import jax

    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


OPS = ("masked_attention", "scatter_add", "gated_delta_rule", "mamba2_scan", "head_turn")


def run(platform: str = "auto", iters: int = 30, ops=OPS) -> dict:
    from distar_tpu.parallel.executor import select_backend

    select_backend(platform)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distar_tpu.ops.pallas_kernels import (
        interpret_fallbacks,
        masked_attention,
        masked_attention_reference,
        scatter_add_onehot,
        scatter_add_reference,
    )

    native = jax.default_backend() == "tpu"
    rng = np.random.default_rng(0)
    rows = []

    def f32(fn):
        # the reference sees the same (possibly bf16-rounded) inputs, in f32
        # with true-f32 matmuls; the impls run at the default precision
        def ref(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*(x.astype(jnp.float32) if jnp.issubdtype(
                    x.dtype, jnp.floating) else x for x in a))
        return ref

    def bench(op, shape, reference, impls, args, grad_argnums, tol):
        """Check every impl against ``reference`` (run in f32), then time it."""
        if op not in ops:
            return
        def grad_of(fn):
            return jax.jit(jax.grad(
                lambda *a: sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in jax.tree.leaves(fn(*a))),
                argnums=grad_argnums))

        reference = f32(reference)
        for label, wrap in (("fwd", jax.jit), ("fwd+bwd", grad_of)):
            want = wrap(reference)(*args)
            fns = {name: wrap(fn) for name, fn in impls.items()}
            us = {}
            for name, fn in fns.items():
                # compared on the device: the learner-shape outputs are GBs
                for g, w in zip(jax.tree.leaves(fn(*args)), jax.tree.leaves(want)):
                    err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
                    bound = tol * max(1.0, float(jnp.max(jnp.abs(w))))
                    if not err <= bound:  # also catches NaN
                        raise AssertionError(
                            f"{op} {label} {shape} {name}: max |err| {err} > {bound}")
                us[name] = _time(fn, args, iters if label == "fwd" else max(iters // 3, 5))
            for name in fns:
                rows.append({
                    "op": op, "pass": label, "shape": shape, "impl": name,
                    "us": round(us[name], 1),
                    "speedup_vs_xla": round(us["xla"] / us[name], 3),
                })

    # flagship geometry: entity transformer head_dim 128 x 2 heads x 512
    # entities; scatter 512 entities x 32-dim onto the 152x160 map. B=8 ~ an
    # actor's env batch, B=384 = the learner step's b6 x t64 flattened batch.
    # Interpret mode (off-TPU) is a python-level emulation: toy shapes there.
    if native:
        H, N, Dh, Hm, Wm, D, batches = 2, 512, 128, 152, 160, 32, (8, 384)
    else:
        H, N, Dh, Hm, Wm, D, batches = 2, 64, 32, 20, 16, 8, (2,)
    hw = Hm * Wm
    # relative to the largest reference value. f32 is NOT 1e-6 territory on a
    # TPU: at the default precision the MXU rounds f32 operands to bf16
    # passes, in XLA's matmuls and in the kernels' alike
    for dtype, tol in ((jnp.bfloat16, 4e-2), (jnp.float32, 2e-2 if native else 2e-3)):
        name = jnp.dtype(dtype).name
        for B in batches:
            q, k, v = (
                jnp.asarray(rng.standard_normal((B, H, N, Dh)), dtype) for _ in range(3)
            )
            mask = jnp.asarray(rng.random((B, N)) > 0.2).at[:, 0].set(True)
            bench(
                "masked_attention", f"{B}x{H}x{N}x{Dh} {name}",
                lambda q, k, v: masked_attention_reference(q, k, v, mask),
                {
                    "xla": lambda q, k, v: masked_attention_reference(q, k, v, mask),
                    "pallas": lambda q, k, v: masked_attention(q, k, v, mask),
                },
                (q, k, v), (0, 1, 2), tol,
            )
            emb = jnp.asarray(rng.standard_normal((B, N, D)), dtype)
            idx = jnp.asarray(rng.integers(0, hw, (B, N)), jnp.int32)
            bench(
                "scatter_add", f"{B}x{N}x{D}->{Hm}x{Wm} {name}",
                lambda e: scatter_add_reference(e, idx, hw),
                {
                    "xla": lambda e: scatter_add_reference(e, idx, hw),
                    "pallas_onehot": lambda e: scatter_add_onehot(e, idx, hw),
                },
                (emb,), (0,), tol,
            )

    # one Gated DeltaNet layer's rule as qwen3_next_train_b2s8k runs it: 2 x 8,192 positions, 16 key and 32 value
    # heads of 128, chunks of 64, bf16 products, decays drawn as the layer draws them, under jax.checkpoint as the
    # decoder layer wraps it (so fwd+bwd holds the rule's replay). "xla" is the form every other platform runs
    from distar_tpu.ops import delta
    from distar_tpu.ops.pallas_kernels import resolve_interpret

    b, S, Hk, Hv, K, V = (2, 8192, 16, 32, 128, 128) if native else (1, 80, 1, 2, 128, 128)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    rate = rng.uniform(0.0, 16.0, Hv) * np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), Hv))
    rule_args = (
        jnp.asarray(unit(rng.standard_normal((b, S, Hk, K))) * K ** -0.5, jnp.bfloat16),
        jnp.asarray(unit(rng.standard_normal((b, S, Hk, K))), jnp.bfloat16),
        jnp.asarray(rng.standard_normal((b, S, Hv, V)), jnp.bfloat16),
        jnp.asarray(-rate * np.exp(0.9 * rng.standard_normal((b, S, Hv))), jnp.float32),
        jnp.asarray(1.0 / (1.0 + np.exp(-0.9 * rng.standard_normal((b, S, Hv)))), jnp.float32),
    )
    bench(
        "gated_delta_rule", f"{b}x{S}x{Hk}/{Hv}x{K} bfloat16",
        lambda *a: delta._rule_xla(*a, 64, jnp.float32, 8),
        {
            "xla": jax.checkpoint(lambda *a: delta._rule_xla(*a, 64, jnp.bfloat16, 8)),
            "pallas": jax.checkpoint(lambda *a: delta._rule_kernel(*a, 64, resolve_interpret(None))),
        },
        rule_args, (0, 1, 2, 3, 4), 4e-2,
    )

    # one Mamba-2 layer's scan as nemotron_twotower_train_b2s8k runs it: 2 x 8,192 positions, 64 heads of 64 in 8
    # groups, state 128, chunks of 128, bf16 products, dt and A drawn as the layer draws them, under jax.checkpoint
    from distar_tpu.ops import ssm

    b, S, H, P, G, N, Q = (2, 8192, 64, 64, 8, 128, 128) if native else (1, 80, 4, 64, 2, 128, 16)
    scan_args = (
        jnp.asarray(rng.standard_normal((b, S, H, P)), jnp.bfloat16),
        jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), H)) * np.exp(0.5 * rng.standard_normal((b, S, H))),
                    jnp.float32),
        -jnp.arange(1.0, H + 1.0),
        jnp.asarray(rng.standard_normal((b, S, G, N)), jnp.bfloat16),
        jnp.asarray(rng.standard_normal((b, S, G, N)), jnp.bfloat16),
    )
    bench(
        "mamba2_scan", f"{b}x{S}x{H}x{P} g{G} n{N} bfloat16",
        lambda *a: ssm._scan_xla(*a, Q, jnp.float32),
        {
            "xla": jax.checkpoint(lambda *a: ssm._scan_xla(*a, Q, jnp.bfloat16)),
            "pallas": jax.checkpoint(lambda x, dt, A, B, C: ssm._scan_kernel(x, dt, dt * A, B, C, Q,
                                                                             resolve_interpret(None))),
        },
        scan_args, (0, 1, 2, 3, 4), 4e-2,
    )

    # one attention layer's q/k preparation as laguna_train_b1s16k runs it, from the projections' results to the core's
    # operands (head norm, rotation, the queries' scale, the kernel's layout), under jax.checkpoint: a sliding layer
    # (36 query heads over 4, the whole head turned) and a full one (24 over 4, YaRN over the first 64 with its factor)
    from distar_tpu.model import default_laguna_config
    from distar_tpu.ops import head_turn, sequence

    turns = default_laguna_config()["rope_parameters"]
    yarn = {k: turns["full_attention"][k] for k in ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow")}
    for layer, Hq, R, inv_freq, factor in (
            ("sliding", 36, 128, sequence.rope_inv_freq(128, turns["sliding_attention"]["rope_theta"]), None),
            ("full", 24, 64, jnp.asarray(sequence.yarn_inv_freq(64, turns["full_attention"]["rope_theta"], **yarn), jnp.float32),
             turns["full_attention"]["attention_factor"])):
        S, Hq, Hkv, D = (16384, Hq, 4, 128) if native else (128, 3, 1, 128)
        turn_args = (jnp.asarray(rng.standard_normal((1, S, Hq * D)), jnp.bfloat16),
                     jnp.asarray(rng.standard_normal((1, S, Hkv * D)), jnp.bfloat16),
                     jnp.asarray(1.0 + 0.1 * rng.standard_normal(D), jnp.float32),
                     jnp.asarray(1.0 + 0.1 * rng.standard_normal(D), jnp.float32))

        def xla(q, k, wq, wk):
            return (sequence.head_turn_xla(q, Hq, wq, inv_freq, factor, D ** -0.5, 1e-6),
                    sequence.head_turn_xla(k, Hkv, wk, inv_freq, factor, None, 1e-6))

        def fused(q, k, wq, wk):
            tables = head_turn.turn_tables(S, D, inv_freq, factor)
            return (head_turn.head_turn(q, Hq, wq, tables, R, D ** -0.5, 1e-6, resolve_interpret(None)),
                    head_turn.head_turn(k, Hkv, wk, tables, R, None, 1e-6, resolve_interpret(None)))

        bench("head_turn", f"{layer} 1x{S}x{Hq}/{Hkv}x{D} r{R} bfloat16", xla,
              {"xla": jax.checkpoint(xla), "pallas": jax.checkpoint(fused)}, turn_args, (0, 1, 2, 3), 4e-2)

    dev = jax.devices()[0]
    return {
        "metric": "pallas-vs-xla kernel check + microbench",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "pallas_mode": "native" if native else "interpret (correctness only)",
        "interpret_fallbacks": int(interpret_fallbacks().value),
        "checked": len(rows),
        "memory_stats": dev.memory_stats(),  # None where the backend has none
        "rows": rows,
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--platform", default="auto", choices=("auto", "cpu", "tpu"))
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--out", default=None)
    p.add_argument("--ops", default=",".join(OPS), help="comma-separated fragments to run")
    args = p.parse_args()
    report = run(args.platform, args.iters, tuple(args.ops.split(",")))
    for r in report["rows"]:
        print(f"  {r['op']:18s} {r['pass']:8s} {r['shape']:32s} {r['impl']:14s} "
              f"{r['us']:10.1f} us   x{r['speedup_vs_xla']:.2f}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "rows"}))


if __name__ == "__main__":
    main()
