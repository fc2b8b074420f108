"""repro.obs — span tracing, the unified metrics registry, and
plan-vs-actual reconciliation for the offload stack.

Three pieces, layered bottom-up:

* :class:`Tracer` (``obs.tracer``) — the thread-safe, ring-buffered
  flight recorder every instrumented layer shares. Off by default; the
  disabled path is one flag test per site, and the chip benchmark's
  untraced runs measure its cost. Spans carry plan-op identity from
  the executor, queue-wait/transfer splits from the ``IOEngine``
  channel threads, hint lifecycles from the coordinators, and the
  waits on the host's critical path where they happen: host<->device
  copies, device waits, the in-flight budget, the α gate and the host
  Adam's reads, update and writes. ``Tracer.span`` also stamps each on
  the JAX profiler's clock; ``totals()`` sums them by name;
  ``export_chrome(path)`` writes Perfetto-loadable Chrome trace-event
  JSON.
* ``build_snapshot`` (``obs.registry``) — the versioned flat
  ``metrics_snapshot()`` both engines expose: one JSON-serializable
  dict subsuming ``stats()``, embedding ``plan_costs`` and the trace's
  per-route aggregates. This schema is the ingestion contract for the
  ROADMAP item-3 autotuner.
* :func:`reconcile` (``obs.reconcile``) — joins a snapshot against
  ``plan_traffic`` byte predictions (must be exact) and
  ``perfmodel.route_seconds`` time predictions, plus the
  stall-attribution fold (:func:`top_stall_stream`).
"""
from repro.obs.reconcile import (Reconciliation, ReconRow, STALL_STREAM,
                                 reconcile, stall_by_stream,
                                 top_stall_stream)
from repro.obs.registry import (SNAPSHOT_VERSION, build_serve_snapshot,
                                build_snapshot, traffic_maps)
from repro.obs.tracer import (CAT_COPY, CAT_HINT, CAT_IO_CHUNK,
                              CAT_IO_QUEUE, CAT_IO_REQ, CAT_IO_REQ_QUEUE,
                              CAT_PLAN, CAT_WAIT, CAT_WORK, EXEC_TRACK,
                              NO_SPAN, Tracer)

__all__ = [
    "Tracer", "CAT_PLAN", "CAT_HINT", "CAT_IO_CHUNK", "CAT_IO_QUEUE",
    "CAT_IO_REQ", "CAT_IO_REQ_QUEUE", "CAT_COPY", "CAT_WAIT", "CAT_WORK",
    "EXEC_TRACK", "NO_SPAN",
    "SNAPSHOT_VERSION", "build_snapshot", "build_serve_snapshot",
    "traffic_maps",
    "Reconciliation", "ReconRow", "STALL_STREAM", "reconcile",
    "stall_by_stream", "top_stall_stream",
]
