"""HBM footprint + timing report for the SL/RL train step across batch sizes.

AOT-lowers and compiles the flagship step at each config on the current
backend and prints XLA's ``memory_analysis()`` (argument/output/temp/total
bytes), optimized/unoptimized flop counts, compile time, and — unless
``--steps 0`` — a 16-step chained re-timing (so the chip is held for the
compiles plus ~16 steps/config). This is the diagnostic for a batch-scaling
cliff (ROADMAP S4): it separates "spills HBM / falls off the fused path"
from a compiler resource limit.

Usage: python tools/memstats.py [--configs 6,16,32] [--unroll 64]
       [--cap 256] [--remat] [--out artifacts/memstats_tpu.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--configs", default="6,12,16,32")
    p.add_argument("--unroll", type=int, default=64)
    p.add_argument("--cap", type=int, default=0, help="entity cap (0 = off)")
    p.add_argument("--remat", action="store_true")
    p.add_argument("--steps", type=int, default=16,
                   help="also TIME this many donated-feedback steps of the "
                        "compiled executable (0 = compile-only). An "
                        "independent, longer-window cross-check of bench.py's "
                        "4-iteration timing.")
    p.add_argument("--out", default="")
    p.add_argument("--mode", default="sl", choices=("sl", "rl"))
    p.add_argument("--platform", default="auto", choices=("auto", "cpu", "tpu"),
                   help="jax platform (auto: jax's own choice, JAX_PLATFORMS "
                        "honoured)")
    args = p.parse_args()

    from distar_tpu.parallel.executor import select_backend

    select_backend(args.platform)
    import jax

    from distar_tpu.learner import RLLearner, SLLearner

    # timing/peak calibration (bench.py's anchor: known-FLOP chained matmul,
    # guarded so a calibration failure never costs the sweep)
    from bench import _calibrate_matmul

    calib = _calibrate_matmul(jax)
    print(f"[memstats] calibration {json.dumps(calib)}", flush=True)

    rows = []
    for b in (int(x) for x in args.configs.split(",")):
        cfg = {
            "common": {"experiment_name": "memstats"},
            "learner": {
                "batch_size": b,
                "unroll_len": args.unroll,
                "save_freq": 10 ** 9,
                "log_freq": 10 ** 9,
                "max_entities": args.cap or None,
                **({"value_pretrain_iters": -1} if args.mode == "rl" else {}),
            },
            "model": {"dtype": "bfloat16", **({"remat": True} if args.remat else {})},
        }
        label = args.mode + f"-b{b}xt{args.unroll}" + (
            f"-e{args.cap}" if args.cap else "") + ("-remat" if args.remat else "")
        print(f"[memstats] {label}: init", flush=True)
        row = {"config": label, "batch": b, "unroll": args.unroll}
        try:
            if args.mode == "rl":
                import jax.numpy as jnp

                learner = RLLearner(cfg)
                data = dict(next(learner._dataloader))
                data.pop("model_last_iter", None)
                batch = learner.shard_batch(learner._cap(data))
                fn_args = (
                    learner.state["params"], learner.state["opt_state"],
                    batch, jnp.asarray(False),
                )
            else:
                learner = SLLearner(cfg)
                data = dict(next(learner._dataloader))
                data.pop("new_episodes", None)
                data.pop("traj_lens", None)
                data = learner._cap(data)
                batch = jax.tree.map(jax.numpy.asarray, data)
                fn_args = (
                    learner.state["params"], learner.state["opt_state"],
                    batch, learner._hidden,
                )
            from distar_tpu.obs.perf import (
                flops_of_compiled, flops_of_lowered, memory_report,
            )

            t0 = time.perf_counter()
            # _train_step is the learner's jitted step (donation + out
            # shardings already applied) — lower exactly what training runs
            lowered = learner._train_step.lower(*fn_args)
            row["trace_s"] = round(time.perf_counter() - t0, 1)
            flops = flops_of_lowered(lowered)
            if flops:
                row["flops_unoptimized"] = flops
            t0 = time.perf_counter()
            compiled = lowered.compile()
            row["compile_s"] = round(time.perf_counter() - t0, 1)
            # executable-level count: post-optimization, the honest MFU
            # numerator (the unoptimized-HLO count can overcount); memory
            # fields come through the same obs/perf.py helper bench.py and
            # the live learner gauges use
            flops = flops_of_compiled(compiled)
            if flops:
                row["flops_optimized"] = flops
            row.update(memory_report(compiled))
            if args.steps > 0:
                # chained re-timing at a longer window than bench's 4 iters:
                # each call consumes the previous call's params/opt (+ the
                # carried hidden state in SL; RL's 4th arg is a static bool)
                def _next(out, prev):
                    carry = out[2] if args.mode == "sl" else prev[3]
                    return (out[0], out[1], batch, carry)

                out = compiled(*fn_args)
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                cur = _next(out, fn_args)
                for _ in range(args.steps):
                    out = compiled(*cur)
                    cur = _next(out, cur)
                jax.block_until_ready(out)
                step_s = (time.perf_counter() - t0) / args.steps
                row["step_time_s"] = round(step_s, 4)
                row["frames_per_sec"] = round(b * args.unroll / step_s, 2)
                if row.get("flops_optimized"):
                    row["implied_tflops"] = round(
                        row["flops_optimized"] / step_s / 1e12, 1
                    )
            del learner, compiled, lowered, batch, fn_args
        except Exception as e:  # keep sweeping: the cliff config may not compile
            row["error"] = repr(e)[:300]
        print(f"[memstats] {json.dumps(row)}", flush=True)
        rows.append(row)

    out = {"metric": f"{args.mode.upper()} step HBM memory analysis + timing",
           "backend": jax.default_backend(),
           "calibration": calib, "rows": rows}
    # a run where EVERY config errored carries no diagnostic value — exit
    # nonzero and write nothing, so a campaign retry loop re-attempts it.
    # Timings alone ARE data (memory/cost introspection can be absent on a
    # backend); any of the three marks the run useful.
    if not any(("total_mb" in r or "flops_optimized" in r or "step_time_s" in r)
               for r in rows):
        print("[memstats] no config produced data; not writing artifact", flush=True)
        sys.exit(1)
    if args.out:
        d = os.path.dirname(args.out)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=1)
        os.replace(tmp, args.out)  # atomic: a kill never leaves a torn file
        print(f"[memstats] wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
