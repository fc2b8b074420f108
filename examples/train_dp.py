"""Data-parallel SSD-offloaded training: R rank workers × R SSD path
sets, ZeRO-style sharded optimizer state, deterministic collectives.

    PYTHONPATH=src python examples/train_dp.py [--ranks 2] [--steps 6]
        [--paths-per-rank 1] [--cap-ssd-mbs 0] [--verify-single-rank]

Each rank owns a contiguous 1/R element range of every tiered vector
(low-precision params, master, momentum, variance) on its OWN I/O
engine + SSD directory set, all-gathers params per layer boundary and
reduce-scatters layer gradients — see `repro.offload.engine`. With
``--verify-single-rank`` the same seed/batches are replayed on the
single-rank engine and the per-step losses are compared bit-for-bit
(they must be identical in f32, §6.5 extended across the DP axis).

Prints per-step loss, each rank's traffic by (category, route) —
validated against `repro.core.traffic.dp_vertical_traffic` in the test
suite — and the aggregate interconnect volume.
"""
import argparse
import os
import tempfile
import time

import jax

from repro.configs import get_config
from repro.core.perfmodel import StorageRatios
from repro.core.traffic import dp_vertical_traffic
from repro.data import SyntheticLM
from repro.offload import IOConfig, OffloadConfig, make_engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=2)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--micro-batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--alpha", type=float, default=0.25)
    ap.add_argument("--paths-per-rank", type=int, default=1)
    ap.add_argument("--cap-ssd-mbs", type=float, default=0.0)
    ap.add_argument("--verify-single-rank", action="store_true")
    args = ap.parse_args()

    cfg = get_config("gpt-tiny")
    M, mb, R = args.microbatches, args.micro_batch, args.ranks
    ocfg_kw = dict(schedule="vertical", num_microbatches=M, micro_batch=mb,
                   seq_len=args.seq, alpha=args.alpha, lr=3e-3,
                   ratios=StorageRatios(ckpt=0.5, param=0.5, opt=0.0))
    bandwidth = {}
    if args.cap_ssd_mbs > 0:
        bandwidth = {"cpu->ssd": args.cap_ssd_mbs * 1e6,
                     "ssd->cpu": args.cap_ssd_mbs * 1e6}

    with tempfile.TemporaryDirectory(prefix="greedysnake_dp_") as root:
        paths = [os.path.join(root, f"nvme{i}")
                 for i in range(R * args.paths_per_rank)]
        eng = make_engine(
            cfg, OffloadConfig(io=IOConfig(paths=paths, bandwidth=bandwidth),
                               **ocfg_kw),
            jax.random.PRNGKey(0), root, num_ranks=R)
        print(f"{R} ranks × {args.paths_per_rank} path(s) each; "
              f"shard bounds {eng.bounds[0]} of P={eng.P} per layer")
        data = SyntheticLM(cfg.vocab_size, seed=0)
        t0 = time.perf_counter()
        losses = []
        for i in range(args.steps):
            loss = eng.train_step(data.batch(M * mb, args.seq))
            losses.append(loss)
            print(f"step {i + 1:3d}  loss {loss:8.4f}")
        eng.finish()
        dt = time.perf_counter() - t0
        print(f"\n{args.steps} steps, {dt / args.steps:.2f} s/step, "
              f"R={R}, alpha={args.alpha}")

        ms = eng.L * eng.P * 4
        cs = cfg.num_layers * mb * args.seq * cfg.d_model * 4
        t = dp_vertical_traffic(ms, cs, M, R, grad_bytes=ms,
                                os_bytes=3 * ms, n_layers=eng.L)
        print(f"closed form per rank/step: param fetch "
              f"{t.param_fetch / 1e9:.3f} GB (2·ms/R), all-gather "
              f"{t.param_allgather / 1e9:.3f} GB, reduce-scatter "
              f"{t.grad_reducescatter / 1e9:.3f} GB")
        for r, snap in enumerate(eng.traffic()):
            print(f"\nrank {r} traffic (GB per category:route):")
            for key, v in sorted(snap.items()):
                if v:
                    print(f"  {key:22s} {v / 1e9:8.3f}")
        agg_ic = sum(v for snap in eng.traffic()
                     for k, v in snap.items() if "net" in k)
        print(f"\naggregate interconnect volume: {agg_ic / 1e9:.3f} GB")
        eng.close()

        if args.verify_single_rank:
            print("\nreplaying on the single-rank engine ...")
            with tempfile.TemporaryDirectory() as d1:
                ref = make_engine(cfg, OffloadConfig(**ocfg_kw),
                                  jax.random.PRNGKey(0), d1)
                data = SyntheticLM(cfg.vocab_size, seed=0)
                ref_losses = [ref.train_step(data.batch(M * mb, args.seq))
                              for _ in range(args.steps)]
                ref.finish()
                ref.close()
            match = losses == ref_losses
            print("bit-identical loss trajectory:", match)
            if not match:
                for i, (a, b) in enumerate(zip(losses, ref_losses)):
                    if a != b:
                        print(f"  step {i + 1}: dp={a!r} single={b!r}")
                raise SystemExit(1)
    print("OK")


if __name__ == "__main__":
    main()
