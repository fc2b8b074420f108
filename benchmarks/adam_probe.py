#!/usr/bin/env python3
"""Host Adam per element: ``CpuAdam.update`` timed at the chip cells'
segment sizes and at a range of thread counts.

    python3 benchmarks/adam_probe.py [--sizes 651000000,217000000,262144]
        [--threads 1,2,4,8,all] [--repeat 2]

Sizes are elements of one update. A StarCoder2-7B layer holds 217 M
parameters: at α = 0.25 its early segment is 163 M and its late one
54 M, and the four layers of the cell sum to 651 M and 217 M a step.
262,144 is one block, which runs inline. For each size and thread count
it prints one JSON line with the best of ``--repeat`` updates in ns per
element; a thread count is the most ``CpuAdam.update`` may use
(``cpu_adam.MAX_THREADS``), which the segment's size and the CPUs may
lower further. A first line gives the host: the CPUs this process may
use and ``MemTotal``. Runs on the host alone (no JAX); needs 16 bytes
of RAM per element of the largest size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

from repro.optim import cpu_adam  # noqa: E402


def mem_total() -> str:
    try:
        for line in Path("/proc/meminfo").read_text().splitlines():
            if line.startswith("MemTotal:"):
                return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return "unknown"


def best_of(fn, repeat: int) -> float:
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", default="651000000,217000000,262144")
    ap.add_argument("--threads", default="1,2,4,8,all")
    ap.add_argument("--repeat", type=int, default=2)
    args = ap.parse_args(argv)
    cpus = len(os.sched_getaffinity(0))
    sizes = [int(s) for s in args.sizes.split(",")]
    counts = [cpus if t == "all" else int(t) for t in args.threads.split(",")]
    print(json.dumps({"cpus": cpus, "os_cpu_count": os.cpu_count(),
                      "MemTotal": mem_total(),
                      "max_threads_in_code": cpu_adam.MAX_THREADS}),
          flush=True)
    n_max = max(sizes)
    pattern = np.random.default_rng(0).standard_normal(1 << 20,
                                                       dtype=np.float32)
    grad = np.resize(pattern * np.float32(1e-3), n_max)
    p = np.resize(pattern * np.float32(2e-2), n_max)
    m = np.zeros(n_max, np.float32)
    v = np.zeros(n_max, np.float32)
    adam = cpu_adam.CpuAdam(lr=1e-3)
    step = [0]
    cap = cpu_adam.MAX_THREADS
    try:
        for n in sizes:
            for t in counts:
                cpu_adam.MAX_THREADS = t

                def one():
                    step[0] += 1
                    adam.update(p, m, v, grad, step[0], 0, n)

                s = best_of(one, args.repeat)
                print(json.dumps({"n": n, "max_threads": t,
                                  "threads": adam.threads_for(n),
                                  "s": round(s, 4),
                                  "ns_per_element": round(s / n * 1e9, 3)}),
                      flush=True)
    finally:
        cpu_adam.MAX_THREADS = cap
    return 0


if __name__ == "__main__":
    sys.exit(main())
