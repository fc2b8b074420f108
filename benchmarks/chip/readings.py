#!/usr/bin/env python3
"""The readings the cell's limits are set from, for the numbers that the
program's own runs cannot give: the control's and the planted faults'.

    python3 benchmarks/chip/readings.py --workload <cell> --seeds 1 2 3

For each seed the plain reference follows the first three steps of a run
(the cell's set-up steps and the window's first) three times, and each
variant is compared with the f32 reference exactly as a
run compares the program:

* ``control``: the reference one precision step below what the
  configuration states (``reference.py``'s ``mode="control"``);
* ``half_batch``: each step trains on half of its rows, the mean taken
  over those.

A step that leaves the state unchanged reads 1 on ``change_gap`` by the
comparison's definition and needs no run. The sound program's readings
come from the benchmark's own runs. Runs on the machine it is started on;
on a TPU, at the cell's own sizes.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import compare  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import traffic_gen  # noqa: E402

VARIANTS = {"control": {"mode": "control"}, "half_batch": {"half_batch": True}}


def readings(cfg_file: dict, cell: dict, seed: int,
             reg: Optional[harness.Registry] = None) -> dict:
    """{variant: compare.numbers(variant, f32 reference)} for one seed,
    with the model module the configuration names."""
    import program
    model = (reg or harness.Registry()).model_of(cfg_file)
    batches = traffic_gen.batches(cell, cfg_file["vocab_size"], seed,
                                  cell["setup_steps"] + 1)
    arch = model.Arch.from_config(cfg_file)
    key = program.seed_key(seed)

    def follow(**kw):
        return reference.run(model, arch, key, cell["lr"],
                             cell["micro_batches"], batches, **kw)
    ref = follow()
    return {name: compare.numbers(follow(**kw), ref)
            for name, kw in VARIANTS.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    reg = harness.Registry()
    cell = reg.cell(args.workload)
    cfg_file = reg.config(cell["config"])
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    for seed in args.seeds:
        out = readings(cfg_file, cell, seed, reg)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": dev.device_kind, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
