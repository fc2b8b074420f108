"""The unified metrics registry: one versioned, flat, JSON-serializable
snapshot of everything the offload stack measures.

``build_snapshot(eng)`` works on both :class:`OffloadEngine` and
:class:`DataParallelOffloadEngine` (both expose it as
``metrics_snapshot()``) and SUBSUMES their ``stats()`` shapes — every
``stats()`` field appears here, normalized to per-rank lists so the
single-rank and DP schemas are the same shape. The dict round-trips
through ``json.dumps`` by construction (numpy ints coerced, tuples
listed): it is the artifact the bench-smoke job persists and the
ingestion contract for the ROADMAP item-3 autotuner, which is why the
schema carries ``version`` (bump ``SNAPSHOT_VERSION`` on any breaking
shape change) and embeds ``plan_costs`` — enough to re-run
``plan_traffic`` from the snapshot alone, so ``obs.reconcile`` needs no
live engine.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

#: Bump on any breaking change to the snapshot shape. Consumers
#: (``obs.reconcile``, ``check_smoke.py``, the future autotuner) must
#: check this before reading.
SNAPSHOT_VERSION = 1


def _rank_stacks(eng) -> list:
    """Per-rank stacks: the DP engine's ``ranks`` list, or the
    single-rank engine itself (same attribute surface)."""
    rks = getattr(eng, "ranks", None)
    return list(rks) if rks is not None else [eng]


def _jsonable(obj):
    """Coerce meter/stat values to plain JSON types (numpy ints from
    ``arr.nbytes`` arithmetic, tuples from ``shard_bounds``)."""
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, bool) or obj is None or isinstance(obj, str):
        return obj
    if isinstance(obj, float):
        return float(obj)
    try:
        return int(obj)          # numpy integer scalars
    except (TypeError, ValueError):
        return obj


def build_snapshot(eng) -> Dict[str, object]:
    """The versioned flat metrics snapshot (see module docstring).

    Keys:

    * identity — ``version``, ``schedule``, ``ranks``, ``steps``
      (completed train steps), ``act_policy``
    * bytes — ``traffic`` (per-rank list of ``"category:route" ->
      bytes`` meter snapshots, the measured side of the reconciliation)
    * storage — ``io`` / ``io_depth`` (per-rank ``IOEngine.metrics_snapshot()`` /
      ``depth()``, including the per-path counters),
      ``host_peak_nbytes`` / ``host_nbytes``, ``bounds`` (DP shard
      ranges, ``None`` single-rank)
    * time — ``op_seconds``, ``stall_s``, ``phase_time``
    * lookahead — ``lookahead`` (the ``lookahead_stats`` shape),
      ``hint_skips`` / ``act_skips`` / ``act_fallbacks``
    * host optimizer — ``opt_inplace_elems`` / ``opt_staged_elems``
      (per-rank master/m/v elements updated through host views and
      through staged copies, over the engine's life)
    * prediction inputs — ``plan_costs`` (``PlanCosts.from_engine``
      as a dict; ``ratios`` nested)
    * spans — ``trace`` (``Tracer.summary()``: enabled flag, span
      count, per-route measured bytes/busy/queue seconds)
    """
    from repro.core.plan import PlanCosts
    from repro.offload.executor import stall_seconds

    rks = _rank_stacks(eng)
    costs = dataclasses.asdict(PlanCosts.from_engine(eng))
    tracer = getattr(eng, "tracer", None)
    trace = tracer.summary() if tracer is not None else \
        {"enabled": False, "spans": 0, "dropped": 0, "routes": {}}
    lookahead = eng._lookahead_stats()
    snap = {
        "version": SNAPSHOT_VERSION,
        "schedule": eng.ocfg.schedule,
        "ranks": int(getattr(eng, "R", 1)),
        "steps": int(eng.step_num),
        "act_policy": eng.act_policy,
        "traffic": [dict(rk.meter.snapshot()) for rk in rks],
        "io": [rk.ioe._collect_stats() for rk in rks],
        "io_depth": [rk.ioe.depth() for rk in rks],
        "host_peak_nbytes": [rk.host.peak_nbytes for rk in rks],
        "host_nbytes": [rk.host.nbytes() for rk in rks],
        "bounds": getattr(eng, "bounds", None),
        "op_seconds": dict(eng.op_seconds),
        "stall_s": stall_seconds(eng.op_seconds),
        "phase_time": dict(eng.phase_time),
        "lookahead": lookahead,
        "hint_skips": int(eng.hint_skips),
        "act_skips": int(eng.act_skips),
        "act_fallbacks": int(eng.act_fallbacks),
        "opt_inplace_elems": [rk.opt_c.inplace_elems for rk in rks],
        "opt_staged_elems": [rk.opt_c.staged_elems for rk in rks],
        "plan_costs": costs,
        "trace": trace,
    }
    # additive: the online autotuner's decision log (attached by
    # repro.offload.autotune.AutotuneController) rides along so a
    # snapshot archives WHY the plan changed mid-run
    log = getattr(eng, "autotune_log", None)
    if log is not None:
        snap["autotune"] = list(log)
    return _jsonable(snap)


def build_serve_snapshot(eng) -> Dict[str, object]:
    """The serve-engine counterpart of :func:`build_snapshot` — same
    versioning and JSON discipline, serve-shaped keys:

    * identity — ``version``, ``schedule`` (``"serve"``), ``steps``
    * bytes — ``traffic`` (per-rank list, single rank), ``predicted``
      (the accumulated per-step ``plan_traffic`` predictions — the
      plan side of the three-way KV invariant), ``plan_costs``
    * kv — block table state (``block_bytes``, ``capacity_blocks``,
      ``used_blocks``, ``x_host``), lifecycle counters (``admitted``
      / ``preempted`` / ``finished`` / ``appends``), per-unit
      ``spills`` / ``fetches`` (the ``traffic.kv_traffic`` closed-form
      inputs), and ``hit_rate`` — the warm-tier fraction of fetched KV
      bytes (1 - ssd->cpu / cpu->gpu; 1.0 when nothing was fetched)
    * serving — ``tokens_decoded``, ``phase_time``, ``waiting`` /
      ``running`` request counts
    * storage/time/spans — ``io``, ``io_depth``, ``host_peak_nbytes``,
      ``host_nbytes``, ``lookahead``, ``trace`` (as in training)
    """
    import dataclasses as _dc

    traffic = dict(eng.meter.snapshot())
    kv_fetch = traffic.get("kv:cpu->gpu", 0)
    kv_ssd = traffic.get("kv:ssd->cpu", 0)
    snap = {
        "version": SNAPSHOT_VERSION,
        "schedule": "serve",
        "ranks": 1,
        "steps": int(eng.step_num),
        "traffic": [traffic],
        "predicted": {f"{c}:{r}": v
                      for (c, r), v in eng.predicted_traffic.items()},
        "plan_costs": _dc.asdict(eng.plan_costs()),
        "kv": {
            "block_bytes": int(eng.scfg.kv_block_bytes),
            "capacity_blocks": int(eng.capacity_blocks),
            "used_blocks": int(eng.used_blocks),
            "x_host": float(eng.scfg.kv_x_host),
            "blocks_per_request": int(eng.blocks_per_request),
            "admitted": int(eng.admitted),
            "preempted": int(eng.preempted),
            "finished": int(eng.finished),
            "appends": int(eng.appends),
            "spills": list(eng.kv_spills),
            "fetches": list(eng.kv_fetches),
            "hit_rate": 1.0 - kv_ssd / kv_fetch if kv_fetch else 1.0,
        },
        "tokens_decoded": int(eng.tokens_decoded),
        "phase_time": dict(eng.phase_time),
        "waiting": sum(1 for r in eng.requests.values()
                       if r.state == "waiting" or r.state == "evicted"),
        "running": sum(1 for r in eng.requests.values()
                       if r.state == "running"),
        "io": [eng.ioe._collect_stats()],
        "io_depth": [eng.ioe.depth()],
        "host_peak_nbytes": [eng.host.peak_nbytes],
        "host_nbytes": [eng.host.nbytes()],
        "lookahead": eng._lookahead_stats(),
        "trace": eng.tracer.summary(),
    }
    return _jsonable(snap)


def traffic_maps(snapshot: dict) -> List[Dict[tuple, int]]:
    """The snapshot's per-rank measured byte counters re-keyed as
    ``(category, route)`` tuples — the join key ``plan_traffic``
    predictions use."""
    out = []
    for rank_map in snapshot["traffic"]:
        m: Dict[tuple, int] = {}
        for key, v in rank_map.items():
            cat, _, route = key.partition(":")
            m[(cat, route)] = int(v)
        out.append(m)
    return out
