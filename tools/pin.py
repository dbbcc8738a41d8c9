#!/usr/bin/env python
"""pin: the multi-process core-pinning harness CLI (distar_tpu.fleet.pinning).

Fleet perf numbers on a shared host are context-switch arithmetic unless
every member process owns its core. This tool plans, applies and verifies
core pinning, and prints the PROVENANCE BLOCK bench artifacts must embed to
claim ``scaling_valid: true`` (tools/perf_gate.py refuses the claim without
it, or with ``host_cores < 2``). On a host without enough cores the plan
REFUSES — the artifact then keeps ``scaling_valid: false`` with the reason
in-band. Wired into ``tools/loadgen.py --mode fleet`` and the chaos drills.

  python tools/pin.py plan --procs 3 [--reserve-client 1] [--require]
        print the assignment plan (JSON); --require exits 3 when refused
  python tools/pin.py pid --pid 12345 --cores 2,3
        pin a live process (taskset -cp equivalent via sched_setaffinity)
  python tools/pin.py exec --cores 0,1 -- cmd args...
        pin THIS process then exec the command on those cores
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distar_tpu.fleet import pinning  # noqa: E402


def cmd_plan(args) -> int:
    p = pinning.plan(args.procs, reserve_client=args.reserve_client)
    print(json.dumps(p.provenance(), indent=1))
    if args.require and not p.pinned:
        return 3
    return 0


def cmd_pid(args) -> int:
    cores = [int(c) for c in args.cores.split(",") if c.strip()]
    ok = pinning.pin_pid(args.pid, cores)
    print(json.dumps({"pid": args.pid, "cores": cores, "pinned": ok}))
    return 0 if ok else 1


def cmd_exec(args) -> int:
    cores = [int(c) for c in args.cores.split(",") if c.strip()]
    if not pinning.pin_pid(0, cores):
        print(json.dumps({"error": "could not pin self", "cores": cores}))
        return 1
    os.execvp(args.cmd[0], args.cmd)
    return 1  # unreachable


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="command", required=True)

    pl = sub.add_parser("plan", help="plan one-core-per-process assignments")
    pl.add_argument("--procs", type=int, required=True,
                    help="fleet processes needing their own core")
    pl.add_argument("--reserve-client", type=int, default=1,
                    help="cores reserved for the driving client side")
    pl.add_argument("--require", action="store_true",
                    help="exit 3 when the host cannot honestly pin")

    pd = sub.add_parser("pid", help="pin a live process")
    pd.add_argument("--pid", type=int, required=True)
    pd.add_argument("--cores", required=True, help="comma core list")

    ex = sub.add_parser("exec", help="pin self, then exec a command")
    ex.add_argument("--cores", required=True, help="comma core list")
    ex.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command to exec (prefix with --)")

    args = p.parse_args()
    if args.command == "exec":
        args.cmd = [c for c in args.cmd if c != "--"]
        if not args.cmd:
            p.error("exec needs a command after --")
    return {"plan": cmd_plan, "pid": cmd_pid, "exec": cmd_exec}[args.command](args)


if __name__ == "__main__":
    raise SystemExit(main())
