"""Host (numpy) Adam for the offload engine — the analogue of
ZeRO-Infinity's ``cpu_adam`` that GreedySnake reuses.

All computation is uniformly vectorised (no scalar tail handling), which
is the paper's §6.5 reproducibility point: loss is bit-identical across
different chunk/partition ratios because every element goes through the
same vectorised code path. Supports partial (chunk-range) updates for the
α-delayed optimizer step.

One update walks its range in blocks of ``BLOCK`` elements and runs the
same sequence of correctly rounded float32 ufuncs on each block, writing
into two per-thread scratch blocks instead of full-length temporaries.
Each element therefore sees the same operations in the same order as an
unblocked update, whatever the block or thread split. A long range is
cut into contiguous runs of blocks, one per thread of a process-wide
pool (numpy releases the GIL inside each ufunc); the caller runs the
first run itself, and a short range runs on the caller alone.
"""
from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: elements per block: 1 MiB of float32
BLOCK = 1 << 18
#: every thread of an update gets at least this many blocks' worth of
#: elements, so a range under twice that runs on the caller alone
BLOCKS_PER_THREAD = 4
#: most threads one update uses: on a TPU v5e host (13 CPUs),
#: `benchmarks/adam_probe.py` reads 2.44 ns per element on one thread,
#: 1.79 on two and 2.0-2.3 on four to thirteen; memory bandwidth, not
#: arithmetic, bounds the pass
MAX_THREADS = 2

_pool: ThreadPoolExecutor | None = None
_pool_lock = threading.Lock()


def _cpus() -> int:
    return len(os.sched_getaffinity(0))


def _get_pool() -> ThreadPoolExecutor:
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(max_workers=max(1, _cpus() - 1),
                                       thread_name_prefix="cpu-adam")
        return _pool


class CpuAdam:
    def __init__(self, lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.0):
        self.lr, self.b1, self.b2, self.eps, self.wd = lr, b1, b2, eps, weight_decay
        self.pooled_updates = 0     # updates split over the pool
        self.inline_updates = 0     # updates run on the caller alone
        self._count_lock = threading.Lock()

    @staticmethod
    def threads_for(n: int) -> int:
        """Threads an update of ``n`` elements uses (1: inline)."""
        return max(1, min(_cpus(), MAX_THREADS,
                          n // (BLOCKS_PER_THREAD * BLOCK)))

    def update(self, master: np.ndarray, m: np.ndarray, v: np.ndarray,
               grad: np.ndarray, step: int,
               lo: int = 0, hi: int | None = None) -> None:
        """In-place Adam on flat f32 arrays, elements [lo, hi)."""
        hi = master.size if hi is None else hi
        if hi <= lo:
            return
        bc1 = 1 - self.b1 ** step
        bc2 = 1 - self.b2 ** step
        threads = self.threads_for(hi - lo)
        with self._count_lock:
            if threads > 1:
                self.pooled_updates += 1
            else:
                self.inline_updates += 1
        if threads == 1:
            self._run(master, m, v, grad, bc1, bc2, lo, hi)
            return
        # contiguous runs of whole blocks, the last one taking the tail
        blocks = -(-(hi - lo) // BLOCK)
        cuts = [lo + (blocks * i // threads) * BLOCK for i in range(threads)]
        cuts.append(hi)
        pool = _get_pool()
        futs = [pool.submit(self._run, master, m, v, grad, bc1, bc2,
                            cuts[i], cuts[i + 1])
                for i in range(1, threads)]
        self._run(master, m, v, grad, bc1, bc2, cuts[0], cuts[1])
        for f in futs:
            f.result()

    def _run(self, master, m, v, grad, bc1, bc2, lo, hi):
        """Adam on elements [lo, hi), block by block: each line below is
        one ufunc of the unblocked update, with ``t`` and ``d`` as its
        temporaries."""
        b1, b2, eps, lr, wd = self.b1, self.b2, self.eps, self.lr, self.wd
        t_buf = np.empty(min(BLOCK, hi - lo), np.float32)
        d_buf = np.empty_like(t_buf)
        for a in range(lo, hi, BLOCK):
            b = min(a + BLOCK, hi)
            p, m_, v_ = master[a:b], m[a:b], v[a:b]
            t, d = t_buf[:b - a], d_buf[:b - a]
            g = grad[a:b]
            if g.dtype != np.float32:
                np.copyto(d, g, casting="unsafe")
                g = d
            np.multiply(m_, b1, out=m_)
            np.multiply(g, 1 - b1, out=t)
            m_ += t
            np.multiply(v_, b2, out=v_)
            np.multiply(g, g, out=t)
            t *= 1 - b2
            v_ += t
            np.divide(v_, bc2, out=d)
            np.sqrt(d, out=d)
            d += eps
            np.divide(m_, bc1, out=t)
            t /= d
            if wd:
                np.multiply(p, wd, out=d)
                t += d
            t *= lr
            p -= t
