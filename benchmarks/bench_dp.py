"""Benchmark data-parallel sharded offload: N ranks × N SSD path sets.

The Fig. 10-style scaling story in three measurements:

1. **Aggregate SSD throughput** (the headline): R rank stacks — each an
   `IOEngine` + `SSDStore` over its OWN path set — fetch/spill their
   1/R shards CONCURRENTLY. Per-path bandwidth is token-bucket paced to
   SSD speed (this container's filesystem runs at page-cache speed, so
   the regime the paper's multi-path claim addresses — one path
   saturated — must be simulated; the pacing is per rank engine, like
   real per-device bandwidth). A correctly concurrent DP stack scales
   aggregate throughput ~R×; a serialized one would stay at 1×.
   Target: >= 1.6x going from R=1 to R=2.
2. **Raw filesystem numbers** (reference): the same concurrent shard
   traffic uncapped. On this 2-core container both configurations are
   memory-bus bound, so expect little scaling — included so the capped
   numbers can't be mistaken for free speedup.
3. **Model curve**: predicted tokens/s for R = 1..8 from
   `iteration_time_vertical_dp` on a GPT-65B-ish workload (the shape of
   the paper's 1.93x-over-ZeRO-Infinity multi-GPU result).

    PYTHONPATH=src python benchmarks/bench_dp.py [--size-mb 96]
        [--ranks 1 2 4] [--cap-mbs 200] [--chunk-kb 1024] [--csv out.csv]
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time
from typing import List, Optional, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from common import Reporter  # noqa: E402

from repro.core.perfmodel import (MachineParams, StorageRatios, Workload,
                                  iteration_time_vertical_dp)
from repro.io import IOConfig, IOEngine, IOPriority
from repro.core.plan import shard_bounds
from repro.offload.stores import SSDStore, TrafficMeter


def _rank_stacks(root: str, R: int, chunk: int,
                 cap: Optional[float]) -> List[SSDStore]:
    bw = {"cpu->ssd": cap, "ssd->cpu": cap} if cap else {}
    stacks = []
    for r in range(R):
        p = os.path.join(root, f"rank{r}")
        eng = IOEngine(IOConfig(paths=[p], chunk_bytes=chunk, bandwidth=bw))
        stacks.append(SSDStore(p, TrafficMeter(), engine=eng))
    return stacks


def measure_aggregate(R: int, nbytes: int, chunk: int,
                      cap: Optional[float], reps: int = 3
                      ) -> Tuple[float, float]:
    """Best-of-reps aggregate (write, read) bytes/s for R ranks moving
    their 1/R shards concurrently — every rank's request is submitted to
    its own engine before any is awaited, exactly like the DP engine's
    shard prefetch."""
    arr = np.random.default_rng(0).integers(0, 255, nbytes, dtype=np.uint8)
    shards = [arr[lo:hi] for lo, hi in shard_bounds(nbytes, R)]
    outs = [np.empty(s.size, np.uint8) for s in shards]
    best_w = best_r = float("inf")
    with tempfile.TemporaryDirectory(prefix="bench_dp_") as root:
        stacks = _rank_stacks(root, R, chunk, cap)
        for rep in range(reps):
            t0 = time.perf_counter()
            reqs = [s.engine.submit(
                        (lambda s=s, sh=sh, rep=rep:
                         s.write(f"x{rep}", sh, "opt")),
                        priority=IOPriority.OPTIMIZER_STATE,
                        nbytes=sh.nbytes)
                    for s, sh in zip(stacks, shards)]
            for q in reqs:
                q.result()
            best_w = min(best_w, time.perf_counter() - t0)
            t0 = time.perf_counter()
            reqs = [s.engine.submit(
                        (lambda s=s, o=o, rep=rep:
                         s.read(f"x{rep}", "opt", out=o)),
                        priority=IOPriority.PARAM_FETCH, nbytes=o.nbytes)
                    for s, o in zip(stacks, outs)]
            for q in reqs:
                q.result()
            best_r = min(best_r, time.perf_counter() - t0)
        for s in stacks:
            s.close()
    return nbytes / best_w, nbytes / best_r


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=int, default=96)
    ap.add_argument("--ranks", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--cap-mbs", type=float, default=200.0)
    ap.add_argument("--chunk-kb", type=int, default=1024)
    ap.add_argument("--csv", default="")
    args = ap.parse_args()

    rep = Reporter()
    nbytes = args.size_mb << 20
    chunk = args.chunk_kb << 10
    cap = args.cap_mbs * 1e6

    # ---- 1. aggregate SSD throughput, per-path SSD-speed pacing ----
    rep.section(f"aggregate throughput, {args.size_mb} MB total, "
                f"per-path cap {args.cap_mbs:.0f} MB/s (simulated SSD)")
    capped = {}
    for R in args.ranks:
        w, r = measure_aggregate(R, nbytes, chunk, cap)
        capped[R] = (w, r)
        rep.add(f"agg_write_MBps_R{R}", f"{w / 1e6:.0f}")
        rep.add(f"agg_read_MBps_R{R}", f"{r / 1e6:.0f}")
    if 1 in capped and 2 in capped:
        sw = capped[2][0] / capped[1][0]
        sr = capped[2][1] / capped[1][1]
        ok = "PASS" if min(sw, sr) >= 1.6 else "FAIL"
        rep.add("agg_scaling_R1_to_R2_write", f"{sw:.2f}",
                f"target >= 1.6x: {ok}")
        rep.add("agg_scaling_R1_to_R2_read", f"{sr:.2f}",
                f"target >= 1.6x: {ok}")

    # ---- 2. raw filesystem (reference; page-cache speed, 2 cores) ----
    rep.section("raw filesystem reference (uncapped)")
    for R in args.ranks:
        w, r = measure_aggregate(R, nbytes, chunk, cap=None)
        rep.add(f"raw_write_GBps_R{R}", f"{w / 1e9:.2f}")
        rep.add(f"raw_read_GBps_R{R}", f"{r / 1e9:.2f}")

    # ---- 3. Fig. 10-style model curve (GPT-65B-ish workload) ----
    rep.section("perf-model scaling curve (GPT-65B-ish, vertical DP)")
    ms = 65e9 * 2
    w65 = Workload(ms=ms, cs=2.6e9, os_bytes=65e9 * 12,
                   grad_bytes=65e9 * 4, flops_per_mb=2 * 65e9 * 2048,
                   tokens_per_mb=2048, n_layers=80)
    m = MachineParams()
    x = StorageRatios(0.3, 0.1, 0.2)
    M = 8
    base = None
    for R in (1, 2, 4, 8):
        t = iteration_time_vertical_dp(w65, m, M, 0.2, x, R=R)
        tp = M * w65.tokens_per_mb / t
        base = base or tp
        rep.add(f"model_tokens_per_s_R{R}", f"{tp:.0f}",
                f"speedup vs R=1: {tp / base:.2f}x")

    if args.csv:
        rep.dump_csv(args.csv)


if __name__ == "__main__":
    main()
