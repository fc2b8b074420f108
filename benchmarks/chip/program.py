"""Drives the system under test: ``repro.offload.make_engine`` ->
``OffloadEngine.train_step`` -> ``execute_plan``, with its coordinators,
``repro.io`` and the host ``CpuAdam``.

Set-up builds one engine from the seed and drives it through the cell's
first steps (the first compiles); the window then runs the same engine.
The comparison reads from it each step's loss, the first gradient from
the Adam first moment after step one, and, once the window has closed
and the last step's alpha-delayed tail has landed, each leaf's change
from its initial value. Streamed layers are read back through their
tier.
"""
from __future__ import annotations

import contextlib
import shutil
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import harness
import reference
import traffic_gen

#: Adam's first-moment decay in both the program and the reference
B1 = reference.B1
#: fresh batches drawn from the seed for the window; later steps reuse
#: them in turn so that no data is made inside the window
WINDOW_POOL = 8


def offload_config(cell: dict, cfg_file: dict):
    from repro.core.perfmodel import StorageRatios
    from repro.offload import OffloadConfig
    return OffloadConfig(
        schedule=cell["schedule"], num_microbatches=cell["micro_batches"],
        micro_batch=cell["micro_batch"], seq_len=cell["seq_len"],
        alpha=cell["alpha"], ratios=StorageRatios(**cell["ratios"]),
        lr=cell["lr"], param_dtype=cfg_file["param_dtype"],
        activation_policy=cell["activation_policy"])


def seed_key(seed: int) -> jax.Array:
    """All 64 bits of ``seed`` as a raw threefry key (``PRNGKey`` keeps
    only the low 32 when 64-bit types are off)."""
    s = seed % (1 << 64)
    return jnp.asarray(np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def _segments(leaves) -> List[tuple]:
    """(name, start, end) of each leaf in a layer's flat vector."""
    out, off = [], 0
    for name, shape in leaves:
        n = int(np.prod(shape))
        out.append((name, off, off + n))
        off += n
    return out


def _norm(x: np.ndarray) -> float:
    return float(np.sqrt(np.sum(np.square(x), dtype=np.float64)))


def _step(eng, tokens) -> float:
    """One training step, ending when the device has finished it."""
    loss = eng.train_step(tokens)
    jax.block_until_ready((eng.embed, eng.unembed, eng.final_norm,
                           eng.head_state))
    return loss


class ProgramRun:
    """One engine, its set-up steps, its window and what they left."""

    def __init__(self, model, cfg_file: dict, cell: dict, seed: int,
                 workdir: Path):
        a = model.Arch.from_config(cfg_file)
        self.segments = [_segments(model.layer_leaves(a, l))
                         for l in range(a.layers)]
        self.cfg = model.arch_config(cfg_file)
        self.ocfg = offload_config(cell, cfg_file)
        self.cell = cell
        self.batches = traffic_gen.batches(
            cell, self.cfg.vocab_size, seed, cell["setup_steps"] + WINDOW_POOL)
        self.workdir = Path(workdir)
        shutil.rmtree(self.workdir, ignore_errors=True)
        self.workdir.mkdir(parents=True)
        self.key = seed_key(seed)
        self.eng = None
        self.readback: Dict[str, int] = {}
        self.losses: List[float] = []

    # ------------------------------------------------------ readback
    @contextlib.contextmanager
    def _metered_readback(self):
        """Reads made for the comparison are metered like any other;
        remember them so the plan-vs-meter reconciliation leaves them
        out."""
        before = self.eng.meter.snapshot()
        yield
        for k, v in harness.delta(self.eng.meter.snapshot(), before).items():
            self.readback[k] = self.readback.get(k, 0) + v

    def _layer_vectors(self, vecs) -> List[np.ndarray]:
        with self._metered_readback():
            return [v.read() for v in vecs]

    def _head(self) -> Dict[str, np.ndarray]:
        return {t: np.asarray(getattr(self.eng, t)).astype(np.float32)
                for t in reference.HEAD_LEAVES}

    def _grad_norms(self) -> Dict[str, float]:
        out = {}
        for l, m in enumerate(self._layer_vectors(self.eng.m_m)):
            for name, lo, hi in self.segments[l]:
                out[reference.leaf_name(l, name)] = _norm(m[lo:hi]) / (1 - B1)
        for t in reference.HEAD_LEAVES:
            m = self.eng.head_state[t]["m"]
            out[t] = float(jnp.sqrt(jnp.sum(jnp.square(m)))) / (1 - B1)
        return out

    # ------------------------------------------------------ phases
    def setup(self, log=lambda msg: None) -> None:
        """Build the engine and drive it through the set-up steps."""
        from repro.offload import make_engine
        t = time.perf_counter()
        self.eng = eng = make_engine(self.cfg, self.ocfg, self.key,
                                     str(self.workdir))
        sizes = [v.n for v in eng.p_vecs]
        declared = [segs[-1][2] for segs in self.segments]
        if sizes != declared:
            raise ValueError(f"the program's layer vectors hold {sizes} "
                             f"elements, the model's leaves {declared}")
        log(f"set-up: make_engine {time.perf_counter() - t!r} s")
        t = time.perf_counter()
        self._master0 = self._layer_vectors(eng.m_master)
        self._head0 = self._head()
        log(f"set-up: initial state read back {time.perf_counter() - t!r} s")
        for i in range(self.cell["setup_steps"]):
            t = time.perf_counter()
            self.losses.append(_step(eng, self.batches[i]))
            log(f"set-up: step {i + 1} {time.perf_counter() - t!r} s")
            if i == 0:
                t = time.perf_counter()
                eng.finish()
                self.grad_norms = self._grad_norms()
                log(f"set-up: first gradient read back "
                    f"{time.perf_counter() - t!r} s")

    def _change_norms(self) -> Dict[str, float]:
        change = {}
        for l, m in enumerate(self._layer_vectors(self.eng.m_master)):
            m0 = self._master0[l]
            for name, lo, hi in self.segments[l]:
                change[reference.leaf_name(l, name)] = _norm(
                    m[lo:hi] - m0[lo:hi])
        for t, now in self._head().items():
            change[t] = _norm(now - self._head0[t])
        del self._master0, self._head0
        return change

    def readings(self) -> dict:
        return {"losses": list(self.losses), "grad_norms": self.grad_norms,
                "change_norms": self.change_norms}

    def counters(self, clock: harness.CompileClock) -> dict:
        eng = self.eng
        return {"traffic": eng.meter.snapshot(),
                "op_seconds": dict(eng.op_seconds),
                "phase_time": dict(eng.phase_time),
                "proc_io": harness.proc_io(),
                "compiles": clock.compiles}

    def window(self, seconds: float, profile_dir: Optional[Path]):
        """Whole steps from the window's start until ``seconds`` have
        passed; with ``profile_dir`` the JAX profiler and the engine's
        span tracer record it. Returns (t0, t1, step seconds, losses,
        sync) where ``sync`` pairs a host-clock reading with the profiler
        annotation taken at the same moment."""
        eng, first = self.eng, self.cell["setup_steps"]
        pool = self.batches[first:]
        sync = None
        if profile_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(profile_dir), profiler_options=opts)
            eng.tracer.clear()
            eng.tracer.enable()
            with jax.profiler.TraceAnnotation("bench.sync"):
                sync = time.perf_counter()

        def step(i):
            with jax.profiler.TraceAnnotation("bench.step"):
                return _step(eng, pool[i % len(pool)])
        try:
            t0, t1, times, losses = harness.run_window(step, seconds)
        finally:
            if profile_dir is not None:
                eng.tracer.disable()
                jax.profiler.stop_trace()
        self.losses += losses
        self.batches_run = self.batches[:first] + [
            pool[i % len(pool)] for i in range(len(times))]
        return t0, t1, times, losses, sync

    def settle(self) -> dict:
        """After ``eng.finish()`` has landed every outstanding transfer and
        optimizer tail (the last step's among them): join the meters against the plan (the
        comparison's readbacks left out), then read each leaf's change
        since its initial value."""
        from repro.obs import reconcile
        eng = self.eng
        snap = eng.metrics_snapshot()
        traffic = snap["traffic"][0]
        for k, v in self.readback.items():
            traffic[k] = traffic.get(k, 0) - v
        rec = reconcile(eng.plan, snap)
        io = snap["io"][0]
        self.change_norms = self._change_norms()
        return {"reconcile": rec, "traffic": dict(traffic),
                "steps": snap["steps"],
                "bytes_mismatch": sum(not r.match for r in rec.rows)
                + len(rec.path_sum_mismatches),
                "act_fallbacks": snap["act_fallbacks"],
                "chunk_retries": io["chunk_retries"],
                "integrity_errors": io["integrity_errors"],
                "exec_spans": [s for s in eng.tracer.spans()
                               if s[0] == "exec"]}

    def close(self) -> None:
        if self.eng is not None:
            self.eng.close()
            self.eng = None
        shutil.rmtree(self.workdir, ignore_errors=True)
