#!/usr/bin/env python3
"""Readings the correctness limits are set from (not run by the
benchmark itself).

    python chipbench/limits.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--fault token_altered|state_unchanged|recent_dropped] [--eps 1e-5]

For each seed, in one process: one run of the cell as the benchmark makes
it (the cell's own load and window), every number its check can hold,
and the same numbers with the reference's fp8 control in the program's
place, on the same prompts and served tokens. With ``--fault`` the run
has that fault planted under the timed path (``chipbench/faults.py``),
and the program's numbers are the fault's reading. A cell's limit lies
above the largest sound program reading and below the smallest control
or fault reading that it is to catch. With ``--eps`` the program's
numbers are also read against the reference at that norm epsilon (the
program's own, where it departs from the configuration file's).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != Path(__file__).resolve().parent]
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    from chipbench import faults, run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    ap.add_argument("--eps", type=float)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            _, _, device, ctx = run.prepare(args.workload, seed,
                                            args.seconds, False)
        except run.Refused as e:
            run.say(f"limits: {e}")
            return 1
        undo = faults.plant(args.fault) if args.fault else None
        driver = run.driver(ctx.traffic)
        try:
            out = driver.run(ctx)
        finally:
            ctx.compile_log.uninstall()
            if undo:
                undo()
        witness = None
        if args.eps is not None and out.served:
            wctx = dataclasses.replace(
                ctx, spec={**ctx.spec, "rms_norm_eps": args.eps})
            witness = driver.numbers(driver.teacher_forced(wctx, out.served))
        print(json.dumps({
            "seed": seed, "fault": args.fault, "correct": out.correct,
            "failed": out.failed, "compared": len(out.served),
            "tokens": sum(len(g) for _, g in out.served),
            "e2e": out.e2e, "program": out.readings.get("numbers"),
            "program_at_eps": witness,
            "control": None if args.fault or not out.served
            else driver.control(ctx, out.served),
            "memory_peak_bytes": out.memory_peak_bytes,
            "device": device}), flush=True)
        del ctx, out
    return 0


if __name__ == "__main__":
    sys.exit(main())
