"""Reduce a JAX profiler trace of the window to the device metrics.

A trace is read into plain events, ``(plane, line, name, start_ns,
duration_ns)``, so the reduction can be checked on a synthesized list.
On a TPU the device planes are ``/device:TPU:<n>``; the ``XLA Ops`` line
holds one event per operation that ran, and ``XLA Modules`` one per
jitted program, under its jit name.

* busy: the union of the operation intervals inside the window, per
  device, averaged over the devices;
* window: from the start of the first ``bench.step`` span to the end of
  the last, on the profiler's own clock;
* device ops: module (jit) seconds inside the window, most first;
* idle gaps: the longest stretches with no operation on device 0, each
  named by the host span that covers most of it.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]   # plane, line, name, t0, dur
Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_SPAN = "bench.step"
SYNC_SPAN = "bench.sync"
TOP = 10


def load(profile_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{profile_dir}/**/*.xplane.pb",
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    return [(p.name, ln.name, ev.name, float(ev.start_ns),
             float(ev.duration_ns))
            for p in pd.planes for ln in p.lines for ev in ln.events]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of disjoint sorted ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _module_name(name: str) -> str:
    """``jit_layer_bwd_res(123)`` -> ``jit_layer_bwd_res``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(events: Sequence[Event],
           host_spans: Sequence[Tuple[str, float, float]] = ()
           ) -> Optional[dict]:
    """The window's device numbers, or None when the trace holds no step
    span or no device operation. ``host_spans`` are (name, t0_ns, t1_ns)
    on the profiler's clock, used to name idle gaps."""
    steps = [(t0, t0 + d) for p, _, n, t0, d in events
             if n == STEP_SPAN and not DEVICE_PLANE.match(p)]
    if not steps:
        return None
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    planes = sorted({p for p, *_ in events if DEVICE_PLANE.match(p)})
    busy: Dict[str, List[Interval]] = {}
    for plane in planes:
        ops = [(t0, t0 + d) for p, ln, _, t0, d in events
               if p == plane and ln == OPS_LINE]
        busy[plane] = union(clip(ops, lo, hi))
    busy = {p: b for p, b in busy.items() if b}
    if not busy:
        return None
    busy_s = sum(sum(b - a for a, b in iv) for iv in busy.values()) \
        / len(busy) / 1e9
    modules: Dict[str, float] = {}
    first = sorted(busy)[0]
    for p, ln, name, t0, d in events:
        if p == first and ln == MODULES_LINE:
            for a, b in clip([(t0, t0 + d)], lo, hi):
                key = _module_name(name)
                modules[key] = modules.get(key, 0.0) + (b - a) / 1e9
    idle = sorted(gaps(busy[first], lo, hi), key=lambda g: g[0] - g[1])
    named = []
    for a, b in idle[:TOP]:
        best, cover = "no host span", 0.0
        for name, s0, s1 in host_spans:
            c = min(b, s1) - max(a, s0)
            if c > cover:
                best, cover = name, c
        named.append([best, (b - a) / 1e9])
    return {"busy_s": busy_s, "window_s": (hi - lo) / 1e9,
            "devices": len(busy),
            "device_ops": [[k, v] for k, v in sorted(
                modules.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": named}


def host_spans_on_trace_clock(events: Sequence[Event], sync_host_s: float,
                              spans) -> List[Tuple[str, float, float]]:
    """Put host-clock spans ``(track, name, cat, t0, t1, args)`` on the
    profiler's clock through the ``bench.sync`` annotation, which was
    opened at host time ``sync_host_s``."""
    sync = [t0 for p, _, n, t0, _ in events if n == SYNC_SPAN]
    if not sync:
        return []
    off = sync[0] - sync_host_s * 1e9
    out = []
    for _track, name, _cat, t0, t1, args in spans:
        if t1 is None:
            continue
        label = name if (args or {}).get("l", -1) < 0 \
            else f"{name} l={args['l']}"
        out.append((label, t0 * 1e9 + off, t1 * 1e9 + off))
    return out


def describe(events: Sequence[Event]) -> Dict[str, Dict[str, int]]:
    """Event counts per line of each device plane, and per plane
    otherwise: what a trace holds, for a first look."""
    out: Dict[str, Dict[str, int]] = {}
    for p, ln, *_ in events:
        key = ln if DEVICE_PLANE.match(p) else "(all lines)"
        out.setdefault(p, {}).setdefault(key, 0)
        out[p][key] += 1
    return out
