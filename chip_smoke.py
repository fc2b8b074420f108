#!/usr/bin/env python3
"""Smoke run of the SSD-offloaded trainer on one TPU chip.

Drives the main training path -- ``repro.offload.make_engine`` ->
``OffloadEngine`` -> ``execute_plan``, with its coordinators, the
``repro.io`` engine and the host ``CpuAdam`` -- in one process:

* Phase A, correctness: gpt-100m at its published widths, f32 params,
  vertical and horizontal schedules, M=4, 2 steps each. The two
  schedules' losses must agree within 1e-4.
* Phase B, the main path at real width: StarCoder2-7B's published widths
  with depth cut to 4 of 32 layers, bf16 params, every tier on SSD,
  vertical, M=4, micro-batch 1, seq 2048, alpha 0.25; one warm-up and two
  timed steps. Losses must be finite (the first near ln(vocab)), every
  measured byte must match the plan's prediction, no activation fallback,
  chunk retry or integrity error may occur, and the layer program's
  output must live on the TPU.

The SSD tier is ``.smoke_ssd/`` next to this file; it is emptied before
and deleted after the run. The printed step times are smoke timings, not
benchmark numbers. The last line of a run that passes every check is
``{"ok": true, "device": {...}}``; any failed check exits non-zero.

    python chip_smoke.py                             # on a TPU host
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse

``--rehearse`` shrinks the widths (depth is kept) and skips the platform
check, for a CPU rehearsal. It never prints the ok line and always exits
non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
SSD_DIR = HERE / ".smoke_ssd"
GIB = 1 << 30
#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
HBM_BYTES = 16 * GIB
M = 4
PHASE_B_DEPTH = 4
#: test_vertical_equals_horizontal_loss's tolerance
SCHEDULE_ATOL = 1e-4


class Checks:
    """Prints each check and remembers the ones that failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {name}: {'ok' if ok else 'FAILED'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and the number
    of backend compiles, from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def __call__(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.compiles += 1


def _shrink(cfg):
    """Rehearsal widths: the config's reduced form at its own depth."""
    return dataclasses.replace(cfg.reduced(), name=cfg.name,
                               num_layers=cfg.num_layers)


def _host_ram() -> str:
    total = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    avail = None
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                avail = int(line.split()[1]) * 1024
    return (f"total {total / GIB:.1f} GiB, available "
            + (f"{avail / GIB:.1f} GiB" if avail is not None else "unknown"))


def _step(eng, tokens) -> float:
    """One training step, ending when the device has finished it."""
    loss = eng.train_step(tokens)
    jax.block_until_ready((eng.embed, eng.unembed, eng.final_norm,
                           eng.head_state))
    return loss


def phase_a(checks: Checks, rehearse: bool) -> None:
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.offload import OffloadConfig, make_engine

    cfg, seq = get_config("gpt-100m"), 1024
    if rehearse:
        cfg, seq = _shrink(cfg), 64
    print(f"phase A: {cfg.name} d_model {cfg.d_model} layers "
          f"{cfg.num_layers} vocab {cfg.vocab_size}, f32, M={M} "
          f"micro-batch 1 seq {seq}", flush=True)
    losses = {}
    for sched in ("vertical", "horizontal"):
        ocfg = OffloadConfig(schedule=sched, num_microbatches=M,
                             micro_batch=1, seq_len=seq,
                             param_dtype="float32")
        eng = make_engine(cfg, ocfg, jax.random.PRNGKey(0),
                          str(SSD_DIR / f"a_{sched}"))
        try:
            data = SyntheticLM(cfg.vocab_size, seed=0)
            losses[sched] = [_step(eng, data.batch(M, seq))
                             for _ in range(2)]
            eng.finish()
        finally:
            eng.close()
        print(f"phase A {sched} losses: {losses[sched]}", flush=True)
    lv, lh = np.asarray(losses["vertical"]), np.asarray(losses["horizontal"])
    diff = float(np.max(np.abs(lv - lh)))
    print(f"phase A max |vertical - horizontal| loss: {diff!r}", flush=True)
    checks("phase A losses finite",
           bool(np.isfinite(lv).all() and np.isfinite(lh).all()))
    checks("phase A vertical == horizontal", diff <= SCHEDULE_ATOL,
           f"max diff {diff!r}, atol {SCHEDULE_ATOL}")


def _ssd_bytes_needed(cfg, ocfg) -> int:
    """SSD bytes phase B writes: params, f32 master/m/v, and the
    boundary checkpoints and inter-layer gradients, per layer."""
    isz = jnp.dtype(ocfg.param_dtype).itemsize
    act = (ocfg.num_microbatches * ocfg.micro_batch * ocfg.seq_len
           * cfg.d_model * isz)
    return cfg.num_layers * (cfg.layer_params(0) * (isz + 3 * 4) + 2 * act)


def phase_b(checks: Checks, rehearse: bool, backend: str,
            clock: CompileClock) -> None:
    from repro.configs import get_config
    from repro.core.perfmodel import StorageRatios
    from repro.data import SyntheticLM
    from repro.obs import reconcile, stall_by_stream
    from repro.offload import OffloadConfig, make_engine

    full = get_config("starcoder2-7b")
    cfg, seq = dataclasses.replace(full, num_layers=PHASE_B_DEPTH), 2048
    print(f"reduced: num_layers {full.num_layers} -> {PHASE_B_DEPTH}",
          flush=True)
    if rehearse:
        cfg, seq = _shrink(cfg), 64
    print(f"phase B: {cfg.name} d_model {cfg.d_model} heads "
          f"{cfg.num_heads}/{cfg.num_kv_heads} head_dim {cfg.head_dim} "
          f"d_ff {cfg.d_ff} vocab {cfg.vocab_size} layers "
          f"{cfg.num_layers}, bf16, all tiers on SSD, vertical, M={M} "
          f"micro-batch 1 seq {seq} alpha 0.25", flush=True)
    ocfg = OffloadConfig(schedule="vertical", num_microbatches=M,
                         micro_batch=1, seq_len=seq, alpha=0.25,
                         ratios=StorageRatios(ckpt=0.0, param=0.0, opt=0.0),
                         param_dtype="bfloat16")
    need = _ssd_bytes_needed(cfg, ocfg)
    free = shutil.disk_usage(SSD_DIR).free
    if not checks("phase B SSD free space", free >= need + GIB,
                  f"need {need / GIB:.2f} GiB + 1 GiB spare, "
                  f"free {free / GIB:.2f} GiB"):
        return

    c0, t0 = clock.seconds, time.perf_counter()
    eng = make_engine(cfg, ocfg, jax.random.PRNGKey(0), str(SSD_DIR / "b"))
    try:
        setup_s = time.perf_counter() - t0
        print(f"phase B engine set-up s: {setup_s!r} (per-layer flat "
              f"params {eng.P})", flush=True)
        data = SyntheticLM(cfg.vocab_size, seed=0)
        batches = [data.batch(M, seq) for _ in range(3)]
        t = time.perf_counter()
        losses = [_step(eng, batches[0])]
        warm_s = time.perf_counter() - t
        print(f"phase B compile s (trace+lower+compile, set-up and "
              f"warm-up): {clock.seconds - c0!r}", flush=True)
        print(f"phase B warm-up step s (incl. compile): {warm_s!r}",
              flush=True)
        n_compiles = clock.compiles
        step_s = []
        for b in batches[1:]:
            t = time.perf_counter()
            losses.append(_step(eng, b))
            step_s.append(time.perf_counter() - t)
        print(f"phase B smoke step s (not a benchmark): {step_s!r}; "
              f"compiles during the timed steps: "
              f"{clock.compiles - n_compiles}", flush=True)
        print(f"phase B losses: {losses!r}", flush=True)
        eng.finish()

        ln_v = math.log(cfg.padded_vocab)
        checks("phase B losses finite", all(map(math.isfinite, losses)))
        checks("phase B first loss near ln(vocab)",
               abs(losses[0] - ln_v) < 1.0,
               f"{losses[0]!r} vs ln({cfg.padded_vocab}) = {ln_v:.4f}")

        snap = eng.metrics_snapshot()
        rec = reconcile(eng.plan, snap)
        bad = [r for r in rec.rows if not r.match]
        checks("phase B plan bytes == measured bytes", rec.ok,
               f"{len(rec.rows)} rows, {len(bad)} mismatched, "
               f"{len(rec.path_sum_mismatches)} path-sum mismatches")
        ops = sorted(snap["op_seconds"].items(), key=lambda kv: -kv[1])
        print(f"phase B host-clock s over {snap['steps']} steps: phases "
              f"{snap['phase_time']}, stall by stream "
              f"{stall_by_stream(snap['op_seconds'])}, top ops {ops[:6]}",
              flush=True)
        io = snap["io"][0]
        for name, val in (("act_fallbacks", snap["act_fallbacks"]),
                          ("chunk_retries", io["chunk_retries"]),
                          ("integrity_errors", io["integrity_errors"])):
            checks(f"phase B {name} == 0", val == 0, f"{val}")

        # after the snapshot: this read is metered
        x = eng.j_embed(eng.embed, jnp.asarray(batches[0][:1]))
        y = eng.j_layer_fwd(jnp.asarray(eng.p_vecs[0].read()), x)
        on = sorted({d.platform for d in y.devices()})
        checks(f"phase B layer output on {backend}", on == [backend],
               f"{on}")
        print(f"phase B host peak bytes (host tier): "
              f"{snap['host_peak_nbytes'][0]}", flush=True)
    finally:
        eng.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny widths; never reports ok")
    args = ap.parse_args()

    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX's backend is {backend!r}, not 'tpu'; this "
              "smoke run needs a TPU (--rehearse runs a CPU rehearsal)",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(HERE / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"host RAM: {_host_ram()}", flush=True)
    shutil.rmtree(SSD_DIR, ignore_errors=True)
    SSD_DIR.mkdir()
    print(f"SSD workdir {SSD_DIR}: free "
          f"{shutil.disk_usage(SSD_DIR).free / GIB:.2f} GiB", flush=True)

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    checks = Checks()
    try:
        for name, run in (("A", lambda: phase_a(checks, args.rehearse)),
                          ("B", lambda: phase_b(checks, args.rehearse,
                                                backend, clock))):
            t = time.perf_counter()
            try:
                run()
            except Exception:
                traceback.print_exc()
                checks(f"phase {name} ran to its end", False)
            print(f"phase {name} wall s: {time.perf_counter() - t!r}",
                  flush=True)
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"device peak_bytes_in_use: {peak} "
              f"(bytes_limit {stats.get('bytes_limit')})", flush=True)
        if not args.rehearse:
            checks("device peak under 16 GiB",
                   peak is not None and peak < HBM_BYTES, f"{peak}")
    finally:
        shutil.rmtree(SSD_DIR, ignore_errors=True)

    if checks.failed:
        print(f"FAILED: {checks.failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        print("rehearsal: every check passed; a rehearsal never reports ok",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
