"""Readings that set the limits of ``bench.check``.

    python3 -m bench.control --workload <cell> --seeds 11,12,13 --seconds 25

Runs the cell once per seed in one process, at the cell's own size and
load (one start-up and one program read for every seed), and prints one
JSON line per seed with the program's numbers and verdict, and the
control's: the float32 reference put in the program's place on the same
answers, judged by the same ``bench.check.verdict``.  The benchmark's own
runs never run the control.  ``--rehearsal`` does the same
on the CPU at rehearsal size.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    args = bench_run.parse(["--workload", a.workload, "--seed", str(seeds[0]),
                            "--seconds", str(a.seconds), "--trace", "0"]
                           + (["--rehearsal"] if a.rehearsal else []))
    cell, cfg, traffic, devs = bench_run.prepare(args)
    from bench import check

    for seed in seeds:
        args.seed = seed
        t0 = time.monotonic()
        res = bench_run.measure(cell, cfg, traffic, devs, args, t0)
        line = res["line"]
        for d in res["details"]:
            print(f"seed {seed} check detail: {d}", file=sys.stderr)
        program = {k: v["value"] for k, v in line["check"].items()}
        control, control_correct = check.control(
            res["items"], missing=program["missing"], compiles=program["compiles"])
        print(json.dumps({
            "seed": seed, "correct": line["correct"],
            "answers": len(res["items"]),
            "schedules": sum(len(it[1]) for it in res["items"]),
            "iterations": [r.iterations for r in res["reports"]],
            "program": program, "control": control,
            "control_correct": control_correct, "limits": check.LIMITS,
            "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
