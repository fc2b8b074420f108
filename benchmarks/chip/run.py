#!/usr/bin/env python3
"""One run of one cell of the offload trainer's chip benchmark.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up builds the engine from the seed and drives it through the cell's
first steps; the window then runs whole steps until ``--seconds`` have
passed. After the window the program is freed and the plain reference
follows the same first steps; ``correct`` compares the two. The last
line of standard output is the result as JSON; the last lines of
standard error are the compared numbers beside their limits.

Exits non-zero, printing no result, without a TPU, with fewer chips than
the cell asks for, or on a ``device_kind`` missing from ``peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def require_chip(chips: int):
    """The first device, or SystemExit when the run has no TPU or too
    few of them. Measurement never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: JAX's devices are {devs[0].platform!r}, "
                         "not 'tpu'; this benchmark runs only on a TPU")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell asks for {chips} chips, JAX "
                         f"sees {len(devs)}")
    return devs[0]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, reg: harness.Registry = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    reg = reg or harness.Registry()
    cell = reg.cell(args.workload)
    cfg_file = reg.config(cell["config"])
    model = reg.model_of(cfg_file)
    dev = require_chip(cell["chips"])
    peaks = reg.peaks(dev.device_kind)

    import jax
    import compare
    import program
    import reference
    import trace_reduce
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = harness.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    log(f"device: {dev.platform} {dev.device_kind!r} x{len(jax.devices())}; "
        f"compile cache {cache}")

    scratch = HERE / ".ssd" / args.workload
    profile_dir = HERE / ".trace" / args.workload if args.trace else None
    if profile_dir is not None:
        shutil.rmtree(profile_dir, ignore_errors=True)
    run = program.ProgramRun(model, cfg_file, cell, args.seed, scratch)
    try:
        run.setup(log)
        log(f"set-up losses {run.losses!r}; compiles so far {clock.compiles}")
        before = run.counters(clock)
        t0, t1, times, losses, sync = run.window(args.seconds, profile_dir)
        setup_s = t0 - T_START
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        compiles_in_window = clock.compiles - before["compiles"]
        t = time.perf_counter()
        run.eng.finish()
        after = run.counters(clock)
        settled = run.settle()
        log(f"settle and read back {time.perf_counter() - t!r} s")
        prog = run.readings()
        batches = run.batches_run
    finally:
        run.close()
    del run
    gc.collect()

    log(f"window: {len(times)} step(s), {t1 - t0!r} s; step s {times!r}")
    log(f"window losses {losses!r}")
    log(f"compiles inside the window: {compiles_in_window}")
    log(settled["reconcile"].format())
    log(f"act_fallbacks {settled['act_fallbacks']}, chunk_retries "
        f"{settled['chunk_retries']}, integrity_errors "
        f"{settled['integrity_errors']}")
    log(f"device peak_bytes_in_use {peak} (bytes_limit "
        f"{stats.get('bytes_limit')})")

    trace = None
    if profile_dir is not None:
        events = trace_reduce.load(str(profile_dir))
        log(f"trace planes: {json.dumps(trace_reduce.describe(events))}")
        spans = trace_reduce.host_spans_on_trace_clock(
            events, sync, settled["exec_spans"])
        trace = trace_reduce.reduce(events, spans)
        del events
        if trace is None:
            raise RuntimeError("the trace holds no step span or no device "
                               "operation inside the window")
        shutil.rmtree(profile_dir, ignore_errors=True)
        log(f"trace: {json.dumps(trace)}")

    t_ref = time.perf_counter()
    ref = reference.run(model, model.Arch.from_config(cfg_file),
                        program.seed_key(args.seed), cell["lr"],
                        cell["micro_batches"], batches)
    log(f"reference: {time.perf_counter() - t_ref!r} s over "
        f"{len(batches)} steps; losses {ref['losses']!r}; program "
        f"losses {prog['losses']!r}")
    nums = compare.numbers(prog, ref)
    nums["bytes_mismatch"] = settled["bytes_mismatch"]
    log(f"worst leaves: grad {nums['worst_grad_leaf']}, change "
        f"{nums['worst_change_leaf']}; left out of change: "
        f"{nums['left_out']}")
    correct, checks = compare.judge(nums, cell["limits"])

    record = {
        "window": {"seconds": t1 - t0, "steps": len(times),
                   "step_s": times},
        "tokens_per_step": cell["micro_batches"] * cell["micro_batch"]
        * cell["seq_len"],
        "setup_s": setup_s,
        "flops_per_token": model.flops_per_token(cfg_file, cell["seq_len"]),
        "peaks": peaks,
        "chips": cell["chips"],
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
        "op_seconds": harness.delta(after["op_seconds"],
                                    before["op_seconds"]),
        "phase_time": harness.delta(after["phase_time"],
                                    before["phase_time"]),
        "traffic": harness.delta(after["traffic"], before["traffic"]),
        "traffic_per_step": {k: v / settled["steps"]
                             for k, v in settled["traffic"].items()},
        "proc_io": harness.delta(after["proc_io"], before["proc_io"]),
        "trace": trace,
    }
    metrics = harness.read_metrics(reg, args.workload, bool(args.trace),
                                   record)
    device = dict(record["device"])
    result = {"correct": correct, "attempted": len(times), "failed": 0,
              "metrics": metrics, "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})"
            f"{'' if c['value'] <= c['limit'] else '  FAILED'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
