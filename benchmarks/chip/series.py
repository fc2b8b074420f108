#!/usr/bin/env python3
"""Run one cell once per seed, each run in a process of its own, one
after another, and keep each run's standard output and error.

    python3 benchmarks/chip/series.py --workload <cell> --seconds 10 \
        --trace 0 --seeds 11 12 13 --out chiprun_out/<dir>

Prints one line per run: seed, exit code, wall seconds, and the result's
``correct``, metrics and compared numbers. Only one process holds the
chip at a time, so this script never imports JAX.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seeds", nargs="+", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    worst = 0
    for seed in args.seeds:
        tag = f"{args.workload}.s{seed}.t{args.trace}"
        t = time.perf_counter()
        p = subprocess.run(
            [sys.executable, str(RUN), "--workload", args.workload,
             "--seed", seed, "--seconds", args.seconds,
             "--trace", args.trace],
            capture_output=True, text=True)
        wall = time.perf_counter() - t
        (out / f"{tag}.out").write_text(p.stdout)
        (out / f"{tag}.err").write_text(p.stderr)
        lines = p.stdout.strip().splitlines()
        res = json.loads(lines[-1]) if p.returncode == 0 and lines else {}
        print(json.dumps({"seed": seed, "rc": p.returncode, "wall_s": wall,
                          "correct": res.get("correct"),
                          "metrics": res.get("metrics"),
                          "checks": res.get("checks")}), flush=True)
        if p.returncode:
            print(p.stderr[-3000:], file=sys.stderr, flush=True)
        worst = max(worst, p.returncode)
    return worst


if __name__ == "__main__":
    sys.exit(main())
