"""What a scope of a compiled program moves, instruction by instruction, from its text alone.

Reads a ``compiled.as_text()`` (``tools/tpu_compile_check.py --hlo-dir``) and lists the instructions of the ENTRY
computation whose ``op_name`` lies under ``--scope``, each with the bytes of its operands and results, split into
the fusions that hold a matrix product and the rest (the "chains": norms, rotations, casts, relayout copies), by
layer (``layer_<i>`` on the path) and by pass (``forward``, ``replay``: under ``rematted_computation``,
``backward``: under ``transpose(jvp``). Bytes only: a text has no times. PERF.md section 5 "PR 45" is this script's
output on ``laguna``'s step before and after the q/k preparation became one pass.

  JAX_PLATFORMS=cpu python tools/tpu_compile_check.py --what laguna --hlo-dir /root/scratch/hlo
  python tools/hlo_scope_bytes.py /root/scratch/hlo/lm_train_step.hlo.txt --scope attn_proj [--list 36,128]
"""
from __future__ import annotations

import argparse
import collections
import json
import re

ITEM = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
        "u64": 8, "f64": 8}
SHAPE = re.compile(r"\b(" + "|".join(ITEM) + r")\[([\d,]*)\]")
HEADER = re.compile(r"^(ENTRY )?%([\w.\-]+) \(.*\{\s*$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*?)\)(?:, |$)")


def shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in SHAPE.findall(text):
        n = ITEM[dtype]
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n
    return total


def read(path: str, scope: str):
    """-> [dict(name, opcode, result, bytes, product, layer, which, op_name)] for the ENTRY instructions under ``scope``."""
    products, results, rows, computation, entry = set(), {}, [], None, False
    for line in open(path):
        header = HEADER.match(line)
        if header:
            computation, entry = header.group(2), bool(header.group(1))
            continue
        if re.search(r" (dot|convolution)\(", line):
            products.add(computation)
        found = INSTRUCTION.match(line) if entry else None
        if not found:
            continue
        name, result, opcode, operands = found.groups()
        results[name] = result
        named = re.search(r'op_name="([^"]*)"', line)
        if not named or f"/{scope}/" not in named.group(1) + "/" or opcode in ("bitcast", "get-tuple-element", "parameter", "constant"):
            continue
        op_name = named.group(1)
        calls = re.search(r"calls=%([\w.\-]+)", line)
        moved = shape_bytes(result) + sum(shape_bytes(results.get(x.strip().lstrip("%"), "")) for x in operands.split(","))
        layer = re.search(r"layer_(\d+)", op_name)
        which = ("replay" if "rematted_computation" in op_name else "backward" if "transpose(jvp" in op_name else "forward")
        rows.append(dict(name=name, opcode=opcode, result=" ".join(f"{d}[{s}]" for d, s in SHAPE.findall(result)), bytes=moved,
                         product=bool(calls and calls.group(1) in products) or opcode in ("dot", "convolution"),
                         layer=int(layer.group(1)) if layer else -1, which=which, op_name=op_name))
    return rows


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("text")
    p.add_argument("--scope", default="attn_proj")
    p.add_argument("--list", default=None, help="also list the instructions whose result holds these dimensions, e.g. 36,128")
    args = p.parse_args()
    rows = read(args.text, args.scope)
    table = collections.defaultdict(lambda: [0, 0])
    for r in rows:
        for key in ((r["layer"], r["which"], r["product"]), ("all", "all", r["product"])):
            table[key][0] += 1
            table[key][1] += r["bytes"]
    for (layer, which, product), (n, moved) in sorted(table.items(), key=lambda kv: str(kv[0])):
        print(f"layer {layer!s:>3} {which:8s} {'products' if product else 'chains':8s} {n:4d} instructions {moved / 1e9:8.3f} GB")
    wide = [r for r in rows if re.search(r"f32\[[\d,]*\d{4,},\d+,\d+\]", r["result"]) and not r["product"]]
    print(json.dumps({"scope": args.scope, "instructions": len(rows),
                      "chains_gb": round(sum(r["bytes"] for r in rows if not r["product"]) / 1e9, 3),
                      "products_gb": round(sum(r["bytes"] for r in rows if r["product"]) / 1e9, 3),
                      "float32_head_results_outside_products": len(wide),
                      "copies": sum(r["opcode"] == "copy" for r in rows)}))
    if args.list:
        for r in rows:
            if args.list in r["result"]:
                print(f"  {r['name']:28s} {r['opcode']:12s} layer {r['layer']} {r['which']:8s} {r['bytes'] / 1e6:8.1f} MB  {r['result'][:90]}")


if __name__ == "__main__":
    main()
