"""Training launcher CLI.

Runs a REAL training loop on the available devices, or an SSD-offloaded
run via the GreedySnake engine (--offload). Without --ssd-dir the SSD
tier is a temporary directory, removed at exit.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gpt-tiny --steps 20
  PYTHONPATH=src python -m repro.launch.train --arch gpt-100m --steps 200 \
      --schedule vertical --offload --alpha 0.2 --microbatches 4
"""
from __future__ import annotations

import argparse
import contextlib
import tempfile
import time

import jax

from repro.configs import get_config, get_smoke
from repro.core.perfmodel import StorageRatios
from repro.core.schedules import ScheduleConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import AdamConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-tiny")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke variant of the arch")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--schedule", default="vertical",
                    choices=["vertical", "horizontal"])
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--alpha", type=float, default=0.0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--offload", action="store_true",
                    help="run through the SSD-offload engine")
    ap.add_argument("--ssd-dir", default=None)
    ap.add_argument("--x-ckpt", type=float, default=0.5)
    ap.add_argument("--x-param", type=float, default=0.5)
    ap.add_argument("--x-opt", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)

    if args.offload:
        from repro.data import SyntheticLM
        from repro.offload import OffloadConfig, make_engine
        with contextlib.ExitStack() as stack:
            workdir = args.ssd_dir or stack.enter_context(
                tempfile.TemporaryDirectory(prefix="greedysnake_ssd_"))
            print(f"SSD tier: {workdir}")
            ocfg = OffloadConfig(
                schedule=args.schedule, num_microbatches=args.microbatches,
                micro_batch=args.batch // args.microbatches,
                seq_len=args.seq, alpha=args.alpha, lr=args.lr,
                ratios=StorageRatios(args.x_ckpt, args.x_param, args.x_opt))
            eng = make_engine(cfg, ocfg, jax.random.PRNGKey(0), workdir)
            stack.callback(eng.close)
            data = SyntheticLM(cfg.vocab_size, seed=0)
            t0 = time.perf_counter()
            for i in range(args.steps):
                loss = eng.train_step(data.batch(args.batch, args.seq))
                print(f"step {i + 1:4d} loss {loss:8.4f}", flush=True)
            eng.finish()
            dt = time.perf_counter() - t0
            print(f"\n{args.steps} steps in {dt:.1f}s "
                  f"({args.steps * args.batch * args.seq / dt:.0f} tokens/s)")
            print("traffic by category (GB):")
            for k, v in sorted(eng.meter.snapshot().items()):
                print(f"  {k:24s} {v / 1e9:10.3f}")
            print("phase seconds:", {k: round(v, 2)
                                     for k, v in eng.phase_time.items()})
    else:
        from repro.train import Trainer
        sched = ScheduleConfig(schedule=args.schedule,
                               num_microbatches=args.microbatches,
                               alpha=args.alpha)
        tr = Trainer(cfg, sched, AdamConfig(lr=args.lr))
        rep = tr.run(args.steps, args.batch, args.seq)
        print(f"\nfinal loss {rep.losses[-1]:.4f}  "
              f"{rep.tokens_per_s:.0f} tokens/s")


if __name__ == "__main__":
    main()
