"""The coordinators of GreedySnake §5 (+ the SSDTrain activation stream).

* ParameterCoordinator — per-layer low-precision params in tiered
  storage; two-stage prefetch (§4.2): the async engine request performs
  the SSD->CPU stage (scheduled by the plan's ``PREFETCH`` hints, up to
  ``prefetch_depth`` fetches ahead), the CPU->device copy happens at
  consumption on the caller's thread, and the device copy is dropped
  after use. ``reset()`` cancels in-flight fetches via the I/O engine's
  cancellation API at a schedule boundary.
* InterLayerTensorCoordinator — activation checkpoints (forward) and
  inter-layer gradients (backward). Checkpoints are written to CPU and
  the (1-x_c) tail streamed to SSD; the forward-pass consumer reads the
  CPU cache (paper: "written to SSD but at the same time cached in
  CPU"), after which the tail is dropped from CPU; the backward-pass
  recompute re-reads the tail from SSD — asynchronously ahead of the
  consumer when a ``PREFETCH_CKPT`` hint fired (``prefetch_bwd``).
  Inter-layer gradients stay in CPU (never SSD).
* OptimizerStepCoordinator — master/momentum/variance in tiered f32
  vectors; the (1-α) fraction updates right after a layer's backward
  (async, overlapped), the α fraction is flushed at the plan EPILOGUE
  and gates the layer's next forward fetch (§4.4 as a cross-iteration
  seam). ``prefetch_late`` (the ``PREFETCH_OPT`` hint) starts the
  α-tail state reads while backward still runs; ``flush_late``
  consumes a landed prefetch, cancels a queued one, and reads the tail
  itself otherwise — byte counters are hint-invariant either way.
  Gradients for the α fraction are retained in CPU memory (the paper
  reuses reclaimed param/ckpt buffers; we meter the bytes the same
  way).

* ActivationCoordinator — the SSDTrain-style activation stream
  (``activation_policy="spill"``): each layer's vjp residuals — the
  non-boundary activations backward needs — are flattened to one byte
  payload after the forward, the ``StorageRatios.act`` head kept in
  CPU and the tail streamed to SSD at ``IOPriority.ACT`` (below ckpt
  spills: strictly opportunistic). The CPU tail copy is dropped as
  soon as the spill is staged (reclaiming DRAM is the point), so every
  backward fetch re-reads the tail. A failed spill or fetch surfaces
  at ``get`` and the executor degrades that one micro-batch to the
  recompute path — the checkpoint tier it needs is still intact.

* KVBlockCoordinator — the serving-time KV-cache block stream
  (``repro.serve``): an evicted request's per-layer cache pytree is
  flattened to one byte payload, padded to a whole number of
  fixed-size blocks (``kv_blocks``), the ``x_host`` head blocks kept
  in CPU and the cold tail streamed to SSD at ``IOPriority.KV``
  (above ckpt spills — a late ``FETCH_KV`` is user-visible decode
  latency). Resume restores every block bitwise: the true payload
  length is kept in coordinator memory, so padding never leaks into
  the rebuilt pytree.

Every coordinator counts lookahead hits/misses (``la_hits`` /
``la_misses``: did the consumer find a completed prefetch?) — the
hit-rate column of the bench-smoke artifact. When the engine attaches
its shared ``repro.obs.Tracer`` (the ``tracer`` attribute, None by
default), each HINTED prefetch additionally records one lifecycle span
from issue to settlement, named ``<stream>:<outcome>`` with outcome
``hit`` (consumer found it landed), ``late`` (consumer waited on it),
``cancelled`` (reset/teardown/queued-cancel before use) or ``unused``
(landed but the consumer had a cheaper source) — co-located with the
``la_hits``/``la_misses`` increments so the trace and the counters can
never disagree. Every host<->device copy goes through ``_to_device`` /
``_to_host``, which, traced, time it as ``exec.h2d`` or as
``exec.device_wait`` then ``exec.d2h``; the waits on other threads'
work (``exec.param_wait``, ``opt.early_wait``, the α gate) and the
optimizer's reads, Adam and writes get spans where they happen.

All three submit their asynchronous work to :class:`repro.io.IOEngine`
rather than raw executors, so a parameter fetch the GPU is about to
block on is scheduled ahead of a deferrable checkpoint spill, and every
transfer is budgeted, cancellable, and (optionally) bandwidth-paced.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import CancelledError
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.io import IOEngine, IOPriority, IORequest
from repro.obs.tracer import (CAT_COPY, CAT_HINT, CAT_WAIT, CAT_WORK,
                              EXEC_TRACK, NO_SPAN)
from repro.offload.stores import HostStore, SSDStore, TieredVector, TrafficMeter
from repro.optim.cpu_adam import CpuAdam


def _hint_issue(coord, key):
    """Open a hint-lifecycle span: remember the issue time (only while
    the engine's tracer is recording — one flag test otherwise)."""
    tr = getattr(coord, "tracer", None)
    if tr is not None and tr.enabled:
        coord._hint_t[key] = time.perf_counter()


def _hint_settle(coord, stream: str, key, outcome: str):
    """Close a hint-lifecycle span with its outcome (hit / late /
    cancelled / unused). No-op for keys never opened (consumer-driven
    fetches, tracing off)."""
    t0 = coord._hint_t.pop(key, None)
    if t0 is None:
        return
    tr = getattr(coord, "tracer", None)
    if tr is None or not tr.enabled:
        return
    l, m = key if isinstance(key, tuple) else (key, -1)
    tr.record(f"hints/{stream}", f"{stream}:{outcome}", CAT_HINT,
              t0, time.perf_counter(), l=int(l), m=int(m), outcome=outcome)


def _xfer(meter: TrafficMeter, engine: IOEngine, category: str, route: str,
          nbytes: int):
    """Meter + (optionally) pace one device-side copy — the single place
    the meter.add/throttle pair lives for non-chunked transfers."""
    meter.add(category, route, nbytes)
    engine.throttle(route, nbytes)


def _tracing(coord):
    """The coordinator's tracer when it is recording, else None."""
    tr = getattr(coord, "tracer", None)
    return tr if tr is not None and tr.enabled else None


def _to_device(coord, arr: np.ndarray, stream: str, l: int = -1,
               m: int = -1) -> jax.Array:
    """Host -> device copy on the calling thread. Traced, the call is
    one ``exec.h2d`` span: the time the caller spends in it, not the
    end of the DMA (no sync is added to find that)."""
    tr = _tracing(coord)
    with tr.span(EXEC_TRACK, "exec.h2d", CAT_COPY, bytes=int(arr.nbytes),
                 stream=stream, l=l, m=m) if tr else NO_SPAN:
        return jnp.asarray(arr)


def _to_host(coord, x, stream: str, l: int = -1, m: int = -1,
             dtype=None) -> np.ndarray:
    """Device -> host copy on the calling thread (then ``astype(dtype)``
    when given). Traced, it is split in two spans: ``exec.device_wait``,
    blocking until ``x`` is computed (the copy would wait for the same,
    so this adds no sync), then ``exec.d2h``, the copy and cast."""
    tr = _tracing(coord)
    if tr is not None:
        with tr.span(EXEC_TRACK, "exec.device_wait", CAT_WAIT,
                     stream=stream, l=l, m=m):
            jax.block_until_ready(x)
    with tr.span(EXEC_TRACK, "exec.d2h", CAT_COPY,
                 bytes=int(getattr(x, "nbytes", 0)), stream=stream, l=l,
                 m=m) if tr else NO_SPAN:
        arr = np.asarray(x)
        return arr if dtype is None else arr.astype(dtype)


def _cancel_or_drain(req: IORequest):
    """Dispose of a request whose result nobody wants: cancel it if
    still queued (no bytes moved), else drain it, swallowing its error
    — the caller has its own data path (fallback, unwind, teardown)."""
    if not req.cancel():
        try:
            req.result()
        except Exception:
            pass


class ParameterCoordinator:
    def __init__(self, vectors: List[TieredVector], meter: TrafficMeter,
                 engine: IOEngine, dtype=np.float16):
        self.vectors = vectors
        self.meter = meter
        self.engine = engine
        self._futures: Dict[int, IORequest] = {}
        self._gate: Dict[int, Callable[[], None]] = {}
        self._gate_ready: Dict[int, Callable[[], bool]] = {}
        self.la_hits = 0        # get() found a completed prefetch
        self.la_misses = 0      # get() had to wait (or submit) the fetch
        self.tracer = None      # engine-attached repro.obs.Tracer
        self._hint_t: Dict[int, float] = {}

    def set_gate(self, l: int, fn: Callable[[], None],
                 ready: Optional[Callable[[], bool]] = None):
        """Barrier that must complete before layer l's params are read
        (used to order the α-delayed optimizer flush before the fetch).

        ``ready`` is the deadlock guard for HINTED fetches: it must
        return True only when waiting on the gate is BOUNDED (the
        gating work is running or done, not still queued). A prefetch
        hint whose gate is not ready is skipped — otherwise a burst of
        ``prefetch_depth`` gated fetch bodies, all outranking the
        queued flushes in the priority heap, could occupy every
        request worker and leave none to run the very flushes they
        wait on. A consumer-driven ``get`` ignores ``ready``: the
        executor blocks instead of a worker, so workers stay free to
        drain the flush."""
        self._gate[l] = fn
        if ready is not None:
            self._gate_ready[l] = ready

    def _fetch(self, l: int) -> np.ndarray:
        """SSD -> host stage only (the two-stage §4.2 pipeline's first
        stage, and everything a prefetch worker should do): wait the α
        gate, then assemble the host vector. The host -> device copy
        stays in :meth:`get` on the consumer thread — doing it on an
        engine worker would steal CPU from the overlapped compute the
        lookahead exists to protect."""
        gate = self._gate.pop(l, None)
        self._gate_ready.pop(l, None)
        tr = _tracing(self)
        track = threading.current_thread().name if tr else ""
        if gate is not None:
            with tr.span(track, "param.gate", CAT_WAIT, l=l) if tr \
                    else NO_SPAN:
                gate()
        with tr.span(track, "param.read", CAT_WORK, l=l) if tr else NO_SPAN:
            return self.vectors[l].read()          # meters ssd->cpu

    def prefetch(self, l: int, consumer: bool = False):
        """Submit layer l's async host fetch. A HINT (``consumer=False``)
        is refused while l's gate is not ready (see :meth:`set_gate`);
        the consumer path always submits — its wait is the executor's,
        not a worker's."""
        if not (0 <= l < len(self.vectors)) or l in self._futures:
            return
        if not consumer:
            ready = self._gate_ready.get(l)
            if l in self._gate and ready is not None and not ready():
                return
        v = self.vectors[l]
        self._futures[l] = self.engine.submit(
            lambda l=l: self._fetch(l),
            priority=IOPriority.PARAM_FETCH, category="param",
            route="ssd->cpu", nbytes=v.n * v.dtype.itemsize)
        if not consumer:
            _hint_issue(self, l)

    def get(self, l: int) -> jax.Array:
        if l not in self._futures:
            self.prefetch(l, consumer=True)
            self.la_misses += 1
        elif self._futures[l].done():
            self.la_hits += 1
            _hint_settle(self, "param", l, "hit")
        else:
            self.la_misses += 1
            _hint_settle(self, "param", l, "late")
        req = self._futures.pop(l)
        tr = _tracing(self)
        with tr.span(EXEC_TRACK, "exec.param_wait", CAT_WAIT, l=l) if tr \
                else NO_SPAN:
            host_arr = req.result()
        dev = _to_device(self, host_arr, "param", l=l)   # "PCIe" copy
        _xfer(self.meter, self.engine, "param", "cpu->gpu", host_arr.nbytes)
        return dev

    def reset(self):
        """Drop all outstanding prefetches at a schedule boundary:
        queued requests are cancelled before they touch storage; a
        running one is drained so its buffers settle. A drained
        request's ERROR is swallowed (``_cancel_or_drain``): nobody
        will consume these futures, and a failed prefetch left in
        ``_futures`` would re-raise a dead step's fault into the next
        step's ``get``."""
        for l, req in self._futures.items():
            _hint_settle(self, "param", l, "cancelled")
            _cancel_or_drain(req)
        self._futures.clear()

    def clear_gates(self):
        """Drop every armed α gate. NOT part of :meth:`reset`: the
        RESET_PARAMS plan op calls ``reset()`` mid-step between waves,
        where the armed gates must survive to order the next wave's
        fetches after their optimizer tails. Only the between-iteration
        plan-swap seam (``apply_plan_config``) and the executor's
        mid-step failure unwind may clear them — at the seam the α
        tails have been flushed and waited; on a failed step the tails
        are abandoned with the step. Either way a stale gate would only
        re-raise a dead step's fault (or deadlock) on the next plan's
        first fetch."""
        self._gate.clear()
        self._gate_ready.clear()


class InterLayerTensorCoordinator:
    """Checkpoints: dict (layer, mb) -> (host_head, ssd_name or None).
    x_c = CPU-resident fraction; the tail beyond k goes to SSD."""

    def __init__(self, x_cpu: float, host: HostStore, ssd: SSDStore,
                 meter: TrafficMeter, engine: IOEngine):
        self.x = x_cpu
        self.host = host
        self.ssd = ssd
        self.meter = meter
        self.engine = engine
        self._pending: Dict[Tuple[str, int, int], IORequest] = {}
        self._shapes: Dict[Tuple[str, int, int], tuple] = {}
        self._device_kept: Dict[Tuple[int, int], jax.Array] = {}
        self._prefetched: Dict[Tuple[int, int], IORequest] = {}  # bwd tails
        self.la_hits = 0        # bwd tail was prefetched and had landed
        self.la_misses = 0      # bwd tail came off the SSD synchronously
        self.tracer = None      # engine-attached repro.obs.Tracer
        self._hint_t: Dict[Tuple[int, int], float] = {}

    def _key(self, kind: str, l: int, m: int) -> str:
        return f"{kind}:{l}:{m}"

    # ---- forward checkpoints ----
    def put_ckpt(self, l: int, m: int, y_dev: jax.Array,
                 keep_on_device: bool = False):
        """Offload layer-l input checkpoint for micro-batch m."""
        if keep_on_device:
            self._device_kept[(l, m)] = y_dev
        arr = _to_host(self, y_dev, "ckpt", l=l, m=m).reshape(-1)
        _xfer(self.meter, self.engine, "ckpt", "gpu->cpu", arr.nbytes)
        self._shapes[("c", l, m)] = y_dev.shape
        k = int(round(self.x * arr.size))
        name = self._key("c", l, m)
        self.host.put(name + ":h", arr[:k].copy())
        self.host.put(name + ":tail", arr[k:].copy())  # CPU cache until consumed
        if k < arr.size:
            old = self._pending.pop(("c", l, m), None)
            if old is not None:
                old.result()    # never two in-flight spills of one name
            # spill via the staging pool: lowest priority, cancellable
            self._pending[("c", l, m)] = self.ssd.write_async(
                name + ":s", arr[k:], "ckpt")

    def get_ckpt_fwd(self, l: int, m: int) -> jax.Array:
        """Next-layer forward input: device-kept or CPU cache (no SSD read).
        Drops the CPU tail afterwards (reclaimed, §4.4)."""
        if (l, m) in self._device_kept:
            return self._device_kept.pop((l, m))
        # §4.2 device-slot discipline: a kept boundary checkpoint is only
        # useful if it is the boundary's FIRST consumer (alternating
        # micro-batch order). A consumer for a different micro-batch means
        # the order was perturbed — the device cannot hold the slot across
        # the whole layer pass, so the kept copy is evicted (its CPU cache
        # already exists) and is re-read like any other micro-batch.
        for k in [k for k in self._device_kept if k[0] == l]:
            del self._device_kept[k]
        name = self._key("c", l, m)
        head = self.host.get(name + ":h")
        tail = self.host.pop(name + ":tail")   # consume CPU cache
        arr = np.concatenate([head, tail])
        _xfer(self.meter, self.engine, "ckpt", "cpu->gpu", arr.nbytes)
        return _to_device(self, arr, "ckpt", l=l, m=m).reshape(
            self._shapes[("c", l, m)])

    def prefetch_bwd(self, l: int, m: int):
        """``PREFETCH_CKPT`` hint: start the backward tail's SSD re-read
        now (ckpt priority) instead of blocking the executor at
        ``get_ckpt_bwd``. No-op when the payload cannot need an SSD
        read — unknown key, CPU-cached tail, fully host-resident head —
        or when the spill itself is still in flight (a request body
        must never wait on another request). Moves the read's bytes
        earlier, never changes them."""
        key = (l, m)
        if key in self._prefetched or ("c", l, m) not in self._shapes:
            return
        name = self._key("c", l, m)
        if name + ":tail" in self.host or name + ":h" not in self.host:
            return
        head = self.host.get(name + ":h")
        n = int(np.prod(self._shapes[("c", l, m)]))
        if head.size >= n:
            return
        wr = self._pending.get(("c", l, m))
        if wr is not None and not wr.done():
            return
        self._prefetched[key] = self.engine.submit(
            lambda: self.ssd.read(name + ":s", "ckpt"),
            priority=IOPriority.CKPT_SPILL, category="ckpt",
            route="ssd->cpu",
            nbytes=(n - head.size) * head.dtype.itemsize)
        _hint_issue(self, key)

    def get_ckpt_bwd(self, l: int, m: int) -> jax.Array:
        """Backward recompute input: CPU head + SSD tail (prefetched by
        a ``PREFETCH_CKPT`` hint when the lookahead pass placed one)."""
        self._device_kept.pop((l, m), None)
        name = self._key("c", l, m)
        req = self._pending.pop(("c", l, m), None)
        if req is not None:
            req.result()
        pre = self._prefetched.pop((l, m), None)
        head = self.host.get(name + ":h")
        shape = self._shapes[("c", l, m)]
        n = int(np.prod(shape))
        if head.size < n:
            if name + ":tail" in self.host:      # never trimmed (x=1 case)
                tail = self.host.get(name + ":tail")
            elif pre is not None:
                hit = pre.done()     # evaluate once: it can flip mid-read
                self.la_hits += hit
                self.la_misses += not hit
                _hint_settle(self, "ckpt", (l, m), "hit" if hit else "late")
                tail = pre.result()
                pre = None
            else:
                self.la_misses += 1
                tail = self.ssd.read(name + ":s", "ckpt")
            arr = np.concatenate([head, tail])
        else:
            arr = head
        if pre is not None:          # prefetched but unused (CPU-cached)
            _hint_settle(self, "ckpt", (l, m), "unused")
            _cancel_or_drain(pre)
        _xfer(self.meter, self.engine, "ckpt", "cpu->gpu", arr.nbytes)
        return _to_device(self, arr, "ckpt", l=l, m=m).reshape(shape)

    def wait_pending(self):
        """Drain all outstanding checkpoint spills (engine teardown)."""
        for req in list(self._pending.values()):
            try:
                req.result()
            except CancelledError:
                pass
        self._pending.clear()

    def clear(self):
        """Abandon every checkpoint / inter-layer gradient this
        coordinator tracks: release device-kept boundary tensors, cancel
        or drain in-flight spills (swallowing their errors — the caller
        is already unwinding), and drop the CPU-resident pieces. Used by
        the plan executor's mid-step failure path so a failed micro-batch
        cannot leak device slots into the next step."""
        self._device_kept.clear()
        for req in list(self._pending.values()):
            if not req.cancel():
                try:
                    req.result()
                except Exception:
                    pass
        self._pending.clear()
        for key, req in list(self._prefetched.items()):
            _hint_settle(self, "ckpt", key, "cancelled")
            _cancel_or_drain(req)
        self._prefetched.clear()
        for kind, l, m in list(self._shapes):
            name = self._key(kind, l, m)
            keys = ([name + ":h", name + ":tail"] if kind == "c"
                    else [name])
            for key in keys:
                if key in self.host:
                    self.host.pop(key)
        self._shapes.clear()

    def drop_ckpt(self, l: int, m: int):
        # A ckpt consumed only via get_ckpt_fwd (the head layer) still has
        # its SSD spill in flight: drain it so no orphan write can race a
        # next-step spill of the same name and counters stay deterministic.
        self._device_kept.pop((l, m), None)
        pre = self._prefetched.pop((l, m), None)
        if pre is not None:
            _hint_settle(self, "ckpt", (l, m), "cancelled")
            _cancel_or_drain(pre)
        req = self._pending.pop(("c", l, m), None)
        if req is not None:
            req.result()
        name = self._key("c", l, m)
        self.host.pop(name + ":h") if name + ":h" in self.host else None
        if name + ":tail" in self.host:
            self.host.pop(name + ":tail")

    # ---- inter-layer gradients (backward; CPU only, §4.3) ----
    def put_grad(self, l: int, m: int, dx_dev: jax.Array,
                 keep_on_device: bool = False):
        if keep_on_device:
            self._device_kept[(-l - 1, m)] = dx_dev
            return
        arr = _to_host(self, dx_dev, "inter_grad", l=l, m=m)
        _xfer(self.meter, self.engine, "inter_grad", "gpu->cpu", arr.nbytes)
        self._shapes[("g", l, m)] = dx_dev.shape
        self.host.put(self._key("g", l, m), arr)

    def get_grad(self, l: int, m: int) -> jax.Array:
        if (-l - 1, m) in self._device_kept:
            return self._device_kept.pop((-l - 1, m))
        # Out-of-order consumer: a kept inter-layer gradient was never
        # written to CPU (that is the whole saving), so losing the device
        # slot forces the spill the alternating order §4.2 avoids — pay
        # the gpu->cpu transfer now, and the cpu->gpu read later.
        for k in [k for k in self._device_kept if k[0] == -l - 1]:
            dx = self._device_kept.pop(k)
            arr = _to_host(self, dx, "inter_grad", l=l, m=k[1])
            _xfer(self.meter, self.engine, "inter_grad", "gpu->cpu",
                  arr.nbytes)
            self._shapes[("g", l, k[1])] = dx.shape
            self.host.put(self._key("g", l, k[1]), arr)
        arr = self.host.pop(self._key("g", l, m))
        _xfer(self.meter, self.engine, "inter_grad", "cpu->gpu", arr.nbytes)
        return _to_device(self, arr, "inter_grad", l=l, m=m).reshape(
            self._shapes[("g", l, m)])


class ActivationCoordinator:
    """Activation (vjp-residual) spill/fetch stream, keyed (layer, mb).

    Layout per key: the flattened residual payload's ``x_act`` head
    lives in the host store (``act:l:m:h``); the tail is written to SSD
    asynchronously (``act:l:m:s``, category ``"act"`` =>
    ``IOPriority.ACT``) and NOT cached — ``get`` re-reads it. The vjp
    treedef and leaf dtypes/shapes stay in coordinator memory (they are
    structure, not data; identical every iteration)."""

    def __init__(self, x_act: float, host: HostStore, ssd: SSDStore,
                 meter: TrafficMeter, engine: IOEngine):
        self.x = x_act
        self.host = host
        self.ssd = ssd
        self.meter = meter
        self.engine = engine
        self._tree: Dict[Tuple[int, int], object] = {}
        self._meta: Dict[Tuple[int, int], list] = {}
        self._k: Dict[Tuple[int, int], int] = {}
        self._n: Dict[Tuple[int, int], int] = {}
        self._pending: Dict[Tuple[int, int], IORequest] = {}     # spills
        self._prefetched: Dict[Tuple[int, int], IORequest] = {}  # reads
        self.la_hits = 0        # get() found a landed tail prefetch
        self.la_misses = 0      # get() read the tail synchronously
        self.tracer = None      # engine-attached repro.obs.Tracer
        self._hint_t: Dict[Tuple[int, int], float] = {}

    def _name(self, l: int, m: int) -> str:
        return f"act:{l}:{m}"

    def put(self, l: int, m: int, vjp):
        """Stream micro-batch m's layer-l residuals out (async tail)."""
        leaves, treedef = jax.tree.flatten(vjp)
        metas, chunks = [], []
        for leaf in leaves:
            arr = _to_host(self, leaf, "act", l=l, m=m)
            # record the TRUE shape first: ascontiguousarray promotes
            # 0-d scalars (slice indices etc.) to (1,), and a scalar
            # restored 1-d would break the vjp's transpose rules
            metas.append((arr.dtype, arr.shape))
            chunks.append(np.ascontiguousarray(arr).reshape(-1)
                          .view(np.uint8))
        buf = np.concatenate(chunks) if chunks else np.zeros(0, np.uint8)
        _xfer(self.meter, self.engine, "act", "gpu->cpu", buf.nbytes)
        key = (l, m)
        k = int(round(self.x * buf.size))
        self._tree[key] = treedef
        self._meta[key] = metas
        self._k[key] = k
        self._n[key] = buf.size
        if k:
            self.host.put(self._name(l, m) + ":h", buf[:k].copy())
        if k < buf.size:
            old = self._pending.pop(key, None)
            if old is not None:
                old.result()    # never two in-flight spills of one name
            self._pending[key] = self.ssd.write_async(
                self._name(l, m) + ":s", buf[k:], "act")

    def prefetch(self, l: int, m: int):
        """Hint: start the tail's SSD read now (ACT priority). No-op if
        there is nothing spilled, or the spill itself is still in
        flight (a request body must never wait on another request)."""
        key = (l, m)
        if key in self._prefetched or key not in self._n:
            return
        k, n = self._k[key], self._n[key]
        if k >= n:
            return
        wr = self._pending.get(key)
        if wr is not None and not wr.done():
            return
        name = self._name(l, m) + ":s"
        self._prefetched[key] = self.engine.submit(
            lambda: self.ssd.read(name, "act"),
            priority=IOPriority.ACT, category="act", route="ssd->cpu",
            nbytes=n - k)
        _hint_issue(self, key)

    def get(self, l: int, m: int):
        """Residuals back on device: host head + SSD tail, rebuilt into
        the vjp pytree. A failed spill surfaces HERE — the executor's
        fallback point for degrading to recompute."""
        key = (l, m)
        name = self._name(l, m)
        req = self._prefetched.pop(key, None)
        wr = self._pending.pop(key, None)
        try:
            if wr is not None:
                wr.result()
        except BaseException:
            if req is not None and not req.cancel():
                try:
                    req.result()
                except Exception:
                    pass        # the spill's error is what propagates
            raise
        k, n = self._k[key], self._n[key]
        if req is not None:
            hit = req.done()         # evaluate once: it can flip mid-read
            self.la_hits += hit
            self.la_misses += not hit
            _hint_settle(self, "act", key, "hit" if hit else "late")
            tail = req.result()
        elif k < n:
            self.la_misses += 1
            tail = self.ssd.read(name + ":s", "act")
        else:
            tail = None
        head = self.host.pop(name + ":h") if k else np.zeros(0, np.uint8)
        if tail is None:
            buf = head
        elif head.size:
            buf = np.concatenate([head, tail])
        else:
            buf = tail
        _xfer(self.meter, self.engine, "act", "cpu->gpu", buf.nbytes)
        leaves, off = [], 0
        for dt, shp in self._meta[key]:
            nb = int(np.prod(shp)) * dt.itemsize
            leaves.append(_to_device(
                self, np.frombuffer(buf[off:off + nb].tobytes(),
                                    dtype=dt).reshape(shp), "act", l=l, m=m))
            off += nb
        vjp = jax.tree.unflatten(self._tree[key], leaves)
        self._forget(key)
        return vjp

    def _forget(self, key):
        for d in (self._tree, self._meta, self._k, self._n):
            d.pop(key, None)

    def drop(self, l: int, m: int):
        """Abandon one key: cancel/drain its in-flight requests
        (swallowing their errors — the caller is falling back) and free
        the host head."""
        key = (l, m)
        _hint_settle(self, "act", key, "cancelled")
        for d in (self._prefetched, self._pending):
            req = d.pop(key, None)
            if req is not None:
                _cancel_or_drain(req)
        name = self._name(l, m)
        if name + ":h" in self.host:
            self.host.pop(name + ":h")
        self._forget(key)

    def clear(self):
        """Abandon everything (mid-plan fault cleanup)."""
        keys = set(self._n) | set(self._pending) | set(self._prefetched)
        for l, m in keys:
            self.drop(l, m)

    def wait_pending(self):
        """Drain outstanding spills/reads (finish/teardown)."""
        for d in (self._pending, self._prefetched):
            for req in list(d.values()):
                try:
                    req.result()
                except (CancelledError, OSError):
                    pass
            d.clear()


class KVBlockCoordinator:
    """Tiered KV-cache block stream, keyed (request, layer-unit).

    Layout per key: the flattened cache payload is padded up to
    ``n_blocks * block_bytes`` (``kv_blocks`` — the SAME ceil the plan
    interpreter and ``traffic.kv_traffic`` price), the
    ``round(x_host * n_blocks)`` head blocks live in the host store
    (``kv:r:l:h``), and the cold tail blocks are written to SSD
    asynchronously (``kv:r:l:s``, category ``"kv"`` =>
    ``IOPriority.KV``). Cache treedef and leaf dtypes/shapes stay in
    coordinator memory — structure, not data. ``get`` rebuilds the
    pytree bitwise from the true (un-padded) payload length."""

    def __init__(self, block_bytes: int, x_host: float, host: HostStore,
                 ssd: SSDStore, meter: TrafficMeter, engine: IOEngine):
        if block_bytes <= 0:
            raise ValueError(f"block_bytes must be > 0, got {block_bytes}")
        self.block_bytes = int(block_bytes)
        self.x = float(x_host)
        self.host = host
        self.ssd = ssd
        self.meter = meter
        self.engine = engine
        self._tree: Dict[Tuple[int, int], object] = {}
        self._meta: Dict[Tuple[int, int], list] = {}
        self._k: Dict[Tuple[int, int], int] = {}       # host head blocks
        self._blocks: Dict[Tuple[int, int], int] = {}  # total blocks
        self._n: Dict[Tuple[int, int], int] = {}       # true payload bytes
        self._pending: Dict[Tuple[int, int], IORequest] = {}     # spills
        self._prefetched: Dict[Tuple[int, int], IORequest] = {}  # reads
        self.la_hits = 0        # get() found a landed tail prefetch
        self.la_misses = 0      # get() read the cold tail synchronously
        self.tracer = None      # engine-attached repro.obs.Tracer
        self._hint_t: Dict[Tuple[int, int], float] = {}

    def _name(self, r: int, l: int) -> str:
        return f"kv:{r}:{l}"

    def blocks_of(self, nbytes: int) -> int:
        from repro.core.traffic import kv_blocks
        return kv_blocks(nbytes, self.block_bytes)

    def put(self, r: int, l: int, caches):
        """SPILL_KV: evict request r's layer-unit-l cache pytree to the
        tiers (all blocks off device; cold tail to SSD, async)."""
        leaves, treedef = jax.tree.flatten(caches)
        metas, chunks = [], []
        for leaf in leaves:
            arr = _to_host(self, leaf, "kv", l=l, m=r)
            metas.append((arr.dtype, arr.shape))
            chunks.append(np.ascontiguousarray(arr).reshape(-1)
                          .view(np.uint8))
        buf = np.concatenate(chunks) if chunks else np.zeros(0, np.uint8)
        bb = self.block_bytes
        nbk = self.blocks_of(buf.size)
        pad = np.zeros(nbk * bb, np.uint8)
        pad[:buf.size] = buf
        _xfer(self.meter, self.engine, "kv", "gpu->cpu", pad.nbytes)
        key = (r, l)
        kb = int(round(self.x * nbk))
        self._tree[key] = treedef
        self._meta[key] = metas
        self._k[key] = kb
        self._blocks[key] = nbk
        self._n[key] = buf.size
        if kb:
            self.host.put(self._name(r, l) + ":h", pad[:kb * bb].copy())
        if kb < nbk:
            old = self._pending.pop(key, None)
            if old is not None:
                old.result()    # never two in-flight spills of one name
            self._pending[key] = self.ssd.write_async(
                self._name(r, l) + ":s", pad[kb * bb:], "kv")

    def prefetch(self, r: int, l: int):
        """``PREFETCH_KV`` hint: start the cold tail's SSD read now (KV
        priority). No-op if nothing is spilled or the spill itself is
        still in flight (a request body must never wait on another
        request)."""
        key = (r, l)
        if key in self._prefetched or key not in self._blocks:
            return
        kb, nbk = self._k[key], self._blocks[key]
        if kb >= nbk:
            return
        wr = self._pending.get(key)
        if wr is not None and not wr.done():
            return
        name = self._name(r, l) + ":s"
        self._prefetched[key] = self.engine.submit(
            lambda: self.ssd.read(name, "kv"),
            priority=IOPriority.KV, category="kv", route="ssd->cpu",
            nbytes=(nbk - kb) * self.block_bytes)
        _hint_issue(self, key)

    def get(self, r: int, l: int):
        """FETCH_KV: restore the cache pytree bitwise — host head
        blocks + SSD cold tail, truncated back to the true payload."""
        key = (r, l)
        name = self._name(r, l)
        req = self._prefetched.pop(key, None)
        wr = self._pending.pop(key, None)
        try:
            if wr is not None:
                wr.result()
        except BaseException:
            if req is not None and not req.cancel():
                try:
                    req.result()
                except Exception:
                    pass        # the spill's error is what propagates
            raise
        kb, nbk = self._k[key], self._blocks[key]
        if req is not None:
            hit = req.done()         # evaluate once: it can flip mid-read
            self.la_hits += hit
            self.la_misses += not hit
            _hint_settle(self, "kv", key, "hit" if hit else "late")
            tail = req.result()
        elif kb < nbk:
            self.la_misses += 1
            tail = self.ssd.read(name + ":s", "kv")
        else:
            tail = None
        head = (self.host.pop(name + ":h") if kb
                else np.zeros(0, np.uint8))
        if tail is None:
            pad = head
        elif head.size:
            pad = np.concatenate([head, tail])
        else:
            pad = tail
        _xfer(self.meter, self.engine, "kv", "cpu->gpu", pad.nbytes)
        buf = pad[:self._n[key]]
        leaves, off = [], 0
        for dt, shp in self._meta[key]:
            nb = int(np.prod(shp)) * dt.itemsize
            leaves.append(_to_device(
                self, np.frombuffer(buf[off:off + nb].tobytes(),
                                    dtype=dt).reshape(shp), "kv", l=l, m=r))
            off += nb
        caches = jax.tree.unflatten(self._tree[key], leaves)
        self._forget(key)
        return caches

    def _forget(self, key):
        for d in (self._tree, self._meta, self._k, self._blocks, self._n):
            d.pop(key, None)

    def drop(self, r: int, l: int):
        """Abandon one key (finished request whose blocks are freed
        without a resume): cancel/drain in-flight requests, free the
        host head, delete the SSD tail."""
        key = (r, l)
        _hint_settle(self, "kv", key, "cancelled")
        pre = self._prefetched.pop(key, None)
        if pre is not None:
            _cancel_or_drain(pre)
        wr = self._pending.pop(key, None)
        if wr is not None:
            try:
                wr.result()   # let the write land, then delete the name
            except Exception:
                pass
        name = self._name(r, l)
        if name + ":h" in self.host:
            self.host.pop(name + ":h")
        kb = self._k.get(key)
        nbk = self._blocks.get(key)
        if kb is not None and nbk is not None and kb < nbk:
            try:
                self.ssd.delete(name + ":s")
            except KeyError:
                pass
        self._forget(key)

    def clear(self):
        """Abandon everything (engine teardown / fault cleanup)."""
        keys = set(self._n) | set(self._pending) | set(self._prefetched)
        for r, l in keys:
            self.drop(r, l)

    def wait_pending(self):
        """Drain outstanding spills/reads (finish/teardown)."""
        for d in (self._pending, self._prefetched):
            for req in list(d.values()):
                try:
                    req.result()
                except (CancelledError, OSError):
                    pass
            d.clear()


class OptimizerStepCoordinator:
    """Per-layer Adam over tiered f32 state vectors with α-delay.
    Each layer's update runs as an OPTIMIZER_STATE-priority engine
    request: its tiered-vector reads/writes become chunked channel ops
    that yield to parameter fetches on the same SSD paths."""

    def __init__(self, masters: List[TieredVector], ms: List[TieredVector],
                 vs: List[TieredVector], params: List[TieredVector],
                 host: HostStore, meter: TrafficMeter,
                 engine: IOEngine, adam: CpuAdam, alpha: float,
                 param_dtype=np.dtype("bfloat16")):
        self.masters, self.ms, self.vs = masters, ms, vs
        self.params = params
        self.host = host
        self.meter = meter
        self.engine = engine
        self.adam = adam
        self.alpha = alpha
        self.param_dtype = param_dtype
        self._early_futs: Dict[int, IORequest] = {}
        self._late_futs: Dict[int, IORequest] = {}
        self._late_pre: Dict[int, IORequest] = {}   # PREFETCH_OPT reads
        self.la_hits = 0        # flush_late consumed a landed prefetch
        self.la_misses = 0      # flush_late read the α-tail itself
        self.tracer = None      # engine-attached repro.obs.Tracer
        self._hint_t: Dict[int, float] = {}

    def _k_early(self, l: int) -> int:
        return int(round((1.0 - self.alpha) * self.masters[l].n))

    def prefetch_late(self, l: int):
        """``PREFETCH_OPT`` hint: start layer l's α-tail state reads
        (master/m/v of [k_early, n)) now, so the next ``flush_late``
        only has to run the Adam segment and the writes. Value-safe
        whenever the previous flush of l has completed (the α gate
        orders it before l's forward fetch) — the concurrent EARLY
        segment only writes the disjoint [0, k_early) ranges. No-op if
        there is no α tail or a hint is already in flight; moves the
        reads earlier, never changes them."""
        if l in self._late_pre:
            return
        n = self.masters[l].n
        k = self._k_early(l)
        if k >= n:
            return

        def work():
            return (self.masters[l].read_range(k, n),
                    self.ms[l].read_range(k, n),
                    self.vs[l].read_range(k, n))

        self._late_pre[l] = self.engine.submit(
            work, priority=IOPriority.OPTIMIZER_STATE, category="opt",
            route="ssd->cpu", nbytes=3 * (n - k) * 4)
        _hint_issue(self, l)

    def submit_early(self, l: int, g_dev: jax.Array, step: int):
        """After layer l's backward: transfer grads, update the (1-α)
        fraction, retain grads for the α fraction (CPU-resident)."""
        g = _to_host(self, g_dev, "grad", l=l, dtype=np.float32)
        _xfer(self.meter, self.engine, "grad", "gpu->cpu", g.nbytes)

        def work():
            n = self.masters[l].n
            k = self._k_early(l)
            if k > 0:
                with self._work_span("opt.state_read", l, "early", k):
                    mast = self.masters[l].read_range(0, k)
                    m_ = self.ms[l].read_range(0, k)
                    v_ = self.vs[l].read_range(0, k)
                with self._work_span("opt.adam", l, "early", k,
                                     threads=self.adam.threads_for(k)):
                    self.adam.update(mast, m_, v_, g[:k], step)
                with self._work_span("opt.state_write", l, "early", k):
                    self._write_range(self.masters[l], mast, 0, k)
                    self._write_range(self.ms[l], m_, 0, k)
                    self._write_range(self.vs[l], v_, 0, k)
                    lowp = mast.astype(self.param_dtype)
                    self._write_range(self.params[l], lowp, 0, k)
            if k < n:
                self.host.put(f"pending_grad:{l}", g[k:].copy())

        self._early_futs[l] = self.engine.submit(
            work, priority=IOPriority.OPTIMIZER_STATE, category="opt",
            route="cpu->ssd", nbytes=g.nbytes)

    def _work_span(self, name: str, l: int, seg: str, n: int, **args):
        """A worker-side span of one Adam segment (``seg`` "early" or
        "late", ``n`` elements) on the running thread's track; ``args``
        (``opt.adam``'s ``threads``) go on the span as well."""
        tr = _tracing(self)
        if tr is None:
            return NO_SPAN
        return tr.span(threading.current_thread().name, name, CAT_WORK,
                       l=l, seg=seg, n=n, **args)

    def _write_range(self, vec: TieredVector, data: np.ndarray, lo: int, hi: int):
        vec.write_seg(data, lo)

    def flush_late(self, l: int, step: int):
        """Flush the remaining α fraction (gate-ordered before layer
        l's next forward fetch). Consumes a ``prefetch_late`` hint's
        state reads when one landed; a still-queued hint is cancelled
        (no bytes moved) and the flush reads the tail itself, so the
        byte counters are hint-invariant either way."""
        f = self._early_futs.pop(l, None)
        if f is not None:
            tr = _tracing(self)
            with tr.span(EXEC_TRACK, "opt.early_wait", CAT_WAIT, l=l) if tr \
                    else NO_SPAN:
                f.result()
        pre = self._late_pre.pop(l, None)
        n = self.masters[l].n
        k = self._k_early(l)
        key = f"pending_grad:{l}"
        if k >= n or key not in self.host:
            if pre is not None:
                _hint_settle(self, "opt", l, "unused")
                _cancel_or_drain(pre)
            return
        g_tail = self.host.pop(key)
        if pre is not None:
            if pre.done():
                self.la_hits += 1
                _hint_settle(self, "opt", l, "hit")
            elif pre.cancel():
                pre = None           # never started: read synchronously
                self.la_misses += 1
                _hint_settle(self, "opt", l, "cancelled")
            else:
                self.la_misses += 1  # running: its bytes are in flight
                _hint_settle(self, "opt", l, "late")
        else:
            self.la_misses += 1

        def work():
            with self._work_span("opt.state_read", l, "late", n - k):
                if pre is not None:
                    # running-or-done by construction (a queued hint was
                    # cancelled above), so this wait is bounded and
                    # cannot deadlock the request workers
                    mast, m_, v_ = pre.result()
                else:
                    mast = self.masters[l].read_range(k, n)
                    m_ = self.ms[l].read_range(k, n)
                    v_ = self.vs[l].read_range(k, n)
            with self._work_span("opt.adam", l, "late", n - k,
                                 threads=self.adam.threads_for(n - k)):
                self.adam.update(mast, m_, v_, g_tail, step)
            with self._work_span("opt.state_write", l, "late", n - k):
                self._write_range(self.masters[l], mast, k, n)
                self._write_range(self.ms[l], m_, k, n)
                self._write_range(self.vs[l], v_, k, n)
                self._write_range(self.params[l],
                                  mast.astype(self.params[l].dtype), k, n)

        self._late_futs[l] = self.engine.submit(
            work, priority=IOPriority.OPTIMIZER_STATE, category="opt",
            route="cpu->ssd", nbytes=g_tail.nbytes)

    def wait_late(self, l: int):
        f = self._late_futs.pop(l, None)
        if f is not None:
            f.result()

    def late_settled(self, l: int) -> bool:
        """Is waiting on layer l's late flush BOUNDED right now — no
        flush outstanding, or its request already running/done (never
        still queued)? The α-gate readiness probe for hinted fetches."""
        f = self._late_futs.get(l)
        return f is None or f.done() or f.running()

    def wait_all(self):
        for l, f in list(self._late_pre.items()):
            _hint_settle(self, "opt", l, "cancelled")
            _cancel_or_drain(f)     # an orphaned hint's error is moot
        self._late_pre.clear()
        for d in (self._early_futs, self._late_futs):
            for f in list(d.values()):
                f.result()
            d.clear()

    def clear(self):
        """Abandon every outstanding flush after a failed step:
        cancel-or-drain all futures (their errors either already
        propagated to the caller or belong to a step being thrown
        away) and drop retained α-tail gradients, so the next step
        cannot consume a stale ``pending_grad`` or trip over a failed
        flush via the α gate. Unlike :meth:`wait_all` this never
        raises. The completed prefix of the in-place Adam update stays
        applied — a failed step is re-run from a checkpoint, not
        resumed."""
        for d in (self._late_pre, self._early_futs, self._late_futs):
            for f in list(d.values()):
                _cancel_or_drain(f)
            d.clear()
        self._hint_t.clear()
        for l in range(len(self.masters)):
            key = f"pending_grad:{l}"
            if key in self.host:
                self.host.pop(key)
