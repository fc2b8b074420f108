#!/usr/bin/env python3
"""Where the host's critical path waits: one traced run of a chip
benchmark cell, with the engine's spans split out.

    python3 benchmarks/span_split.py --workload <cell> --seed <n> \
        --seconds <s> [--out <file.json>]

Runs ``benchmarks/chip/run.py`` as it is, with ``--trace 1``, and keeps
two things that run records and does not report: the engine's span ring
(``repro.obs.Tracer``) and the profiler trace with its host threads
apart. The run's own output is unchanged; one more line, ``SPLIT
{...}``, goes to standard error and to ``--out``:

* ``per_step``: seconds per window step of each span name
  (``Tracer.totals()``; worker spans are busy seconds summed over
  threads, not wall time);
* ``ops``: for ``FETCH_PARAM``, ``WRITEBACK_GRAD`` and ``OPT_LATE``,
  seconds per step on the profiler's clock, how they split into the
  executor thread's leaf spans, and the share of them under a leaf;
* ``idle_gaps``: the longest device idle gaps, each named by the
  innermost program span the profiler recorded on the executor thread
  that covers most of it and, for a wait, by the worker span the wait
  ended on;
* ``clock_check``: the largest distance, in ms, between an executor op
  span in the profiler's trace and the same span mapped from the ring
  through the ``bench.sync`` annotation;
* ``adam_threads``: how many ``opt.adam`` spans ran on each thread
  count, per segment (``"early 8": 8``);
* ``opt_inplace_share``: the share of master/m/v elements the Adam
  segments updated in place through host views, from the
  ``opt.state_read`` spans' ``inplace`` and ``n`` (None where the spans
  carry no ``inplace``);
* ``tokens_per_s`` of the traced window, and ``dropped`` spans.

It needs the chip, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import glob
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE / "chip"))
sys.path.insert(0, str(HERE.parent / "src"))

import harness  # noqa: E402
import program  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

#: the executor op kinds that hold the host-bound step
OPS = ("FETCH_PARAM", "WRITEBACK_GRAD", "OPT_LATE")
#: the executor thread's leaf spans
LEAVES = ("exec.h2d", "exec.device_wait", "exec.d2h", "exec.param_wait",
          "opt.early_wait", "io.budget_wait")
#: leaves that wait on another thread's work
WAITS = ("exec.param_wait", "opt.early_wait", "io.budget_wait")
#: how far before a wait's end the worker span it ended on may close (ns)
END_SLACK_NS = 5e6
TOP = 10

_seen: dict = {}


class _Run(program.ProgramRun):
    """The program's run, keeping its window and its span ring."""

    def window(self, seconds, profile_dir):
        out = super().window(seconds, profile_dir)
        _seen["window"], _seen["sync"] = out[:3], out[4]
        return out

    def settle(self):
        tr = self.eng.tracer
        _seen["spans"], _seen["dropped"] = tr.spans(), tr.dropped
        _seen["totals"] = tr.totals()
        return super().settle()


def host_lines(profile_dir: str, names) -> dict:
    """The named host events of the newest trace, per thread line, and
    the first device's operation intervals: ``{"lines": [[(name, t0, t1,
    args), ...], ...], "device": [(t0, t1), ...]}`` in ns."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{profile_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    lines, device = [], None
    for p in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(p.name):
            if device is None or p.name < device[0]:
                device = (p.name, [
                    (ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ln in p.lines if ln.name == trace_reduce.OPS_LINE
                    for ev in ln.events])
            continue
        for ln in p.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in ln.events if ev.name in names]
            if evs:
                lines.append(evs)
    return {"lines": lines, "device": device[1] if device else []}


def _label(ev) -> str:
    a = ev[3]
    return ev[0] + "".join(f" {k}={a[k]}" for k in ("l", "kind", "m", "seg")
                           if k in a and a[k] != -1)


def _covered(intervals, lo, hi) -> float:
    return sum(b - a for a, b in trace_reduce.union(
        trace_reduce.clip(intervals, lo, hi)))


def _ended_on(wait, workers) -> str:
    """The worker span that ``wait`` ended on: the request (or work
    span) that closed last before the wait did, with the work span
    inside it that names its layer. Chunk transfers, which hold no
    budget and answer no wait, are left out."""
    ended = sorted((w for w in workers if "path" not in w[3]
                    and wait[2] - END_SLACK_NS <= w[2] <= wait[2]),
                   key=lambda w: -w[2])
    if not ended:
        return "no worker span"
    work = next((w for w in ended if "l" in w[3]), None)
    label = _label(ended[0])
    if work is not None and work is not ended[0]:
        label += f" / {_label(work)}"
    return f"{_label(wait)} <- {label}"


def split(trace: dict, spans, sync_host_s: float, steps: int) -> dict:
    """The ``ops``, ``idle_gaps`` and ``clock_check`` parts."""
    lines = trace["lines"]
    ex = next(ln for ln in lines if any(e[0] == trace_reduce.STEP_SPAN
                                        for e in ln))
    steps_ev = [e for e in ex if e[0] == trace_reduce.STEP_SPAN]
    lo, hi = min(e[1] for e in steps_ev), max(e[2] for e in steps_ev)
    leaves = [e for e in ex if e[0] in LEAVES]
    ops = {}
    for op in OPS:
        evs = [e for e in ex if e[0] == op]
        total = sum(e[2] - e[1] for e in evs)
        by_leaf = {}
        for e in evs:
            for lf in leaves:
                if e[1] <= lf[1] and lf[2] <= e[2]:
                    by_leaf[lf[0]] = by_leaf.get(lf[0], 0.0) \
                        + (lf[2] - lf[1])
        under = sum(_covered([(lf[1], lf[2]) for lf in leaves], e[1], e[2])
                    for e in evs)
        by_kind = {}
        for e in evs:
            if "kind" in e[3]:
                by_kind[e[3]["kind"]] = by_kind.get(e[3]["kind"], 0.0) \
                    + (e[2] - e[1]) / 1e9 / steps
        ops[op] = {"s_per_step": total / 1e9 / steps,
                   "kind_s_per_step": by_kind,
                   "leaves_s_per_step": {k: v / 1e9 / steps
                                         for k, v in by_leaf.items()},
                   "under_leaf_pct": 100.0 * under / total if total else None}
    # idle gaps of the first device, named from the executor thread
    busy = trace_reduce.union(trace_reduce.clip(trace["device"], lo, hi))
    gaps = sorted(trace_reduce.gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
    prog = [e for e in ex if e[0] not in (trace_reduce.STEP_SPAN,
                                          trace_reduce.SYNC_SPAN)]
    workers = [e for ln in lines if ln is not ex for e in ln]
    named = []
    for a, b in gaps[:TOP]:
        cover = [(min(b, e[2]) - max(a, e[1]), e) for e in prog
                 if e[2] > a and e[1] < b]
        row = {"s": (b - a) / 1e9, "span": "no program span"}
        if cover:
            best = max(c for c, _ in cover)
            near = [e for c, e in cover if c >= 0.9 * best]
            outer = max(near, key=lambda e: e[2] - e[1])
            inner = min((e for e in near if outer[1] <= e[1]
                         and e[2] <= outer[2]), key=lambda e: e[2] - e[1])
            row["span"], row["op"] = _label(inner), _label(outer)
            row["leaves_s"] = {}
            for c, e in cover:
                if e[0] in LEAVES:
                    row["leaves_s"][e[0]] = row["leaves_s"].get(e[0], 0.0) \
                        + c / 1e9
            wait = max(((c, e) for c, e in cover if e[0] in WAITS),
                       default=None, key=lambda ce: ce[0])
            if wait is not None:
                row["ended_on"] = _ended_on(wait[1], workers)
        named.append(row)
    # the ring's executor op spans, mapped through bench.sync, against
    # the same spans stamped by the profiler
    sync = [e[1] for ln in lines for e in ln
            if e[0] == trace_reduce.SYNC_SPAN]
    worst, pairs = None, 0
    if sync:
        off = sync[0] - sync_host_s * 1e9
        ring = {}
        for track, name, cat, t0, t1, _ in spans:
            if track == "exec" and cat == "plan" and t1 is not None:
                ring.setdefault(name, []).append((t0 * 1e9 + off,
                                                  t1 * 1e9 + off))
        worst = 0.0
        for name, ivs in ring.items():
            prof = sorted((e[1], e[2]) for e in ex if e[0] == name)
            if len(prof) != len(ivs):
                continue
            for (a0, a1), (b0, b1) in zip(sorted(ivs), prof):
                worst = max(worst, abs(a0 - b0), abs(a1 - b1))
                pairs += 1
        worst /= 1e6
    return {"ops": ops, "idle_gaps": named,
            "clock_check": {"max_ms": worst, "spans": pairs}}


def adam_threads(spans) -> dict:
    """``opt.adam`` spans counted by segment and thread count."""
    out: dict = {}
    for _track, name, _cat, _t0, _t1, a in spans:
        if name == "opt.adam":
            key = f"{a.get('seg')} {a.get('threads', '?')}"
            out[key] = out.get(key, 0) + 1
    return dict(sorted(out.items()))


def opt_inplace_share(spans):
    """Elements updated through host views ÷ all master/m/v elements of
    the ``opt.state_read`` spans (three vectors of ``n`` each), or None
    when no such span carries ``inplace``."""
    inplace = total = 0
    for _track, name, _cat, _t0, _t1, a in spans:
        if name == "opt.state_read" and "inplace" in a:
            inplace += a["inplace"]
            total += 3 * a["n"]
    return inplace / total if total else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    load = trace_reduce.load

    def load_and_keep(profile_dir):
        names = {s[1] for s in _seen["spans"]} | {
            trace_reduce.STEP_SPAN, trace_reduce.SYNC_SPAN}
        _seen["trace"] = host_lines(profile_dir, names)
        return load(profile_dir)

    program.ProgramRun, trace_reduce.load = _Run, load_and_keep
    reg = harness.Registry()
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"], reg)
    cell = reg.cell(args.workload)
    t0, t1, times = _seen["window"]
    steps = len(times)
    tokens = cell["micro_batches"] * cell["micro_batch"] * cell["seq_len"]
    out = {"workload": args.workload, "seed": args.seed,
           "tokens_per_s": steps * tokens / (t1 - t0), "steps": steps,
           "dropped": _seen["dropped"],
           "per_step": {k: v / steps for k, v in
                        sorted(_seen["totals"].items())},
           "adam_threads": adam_threads(_seen["spans"]),
           "opt_inplace_share": opt_inplace_share(_seen["spans"]),
           **split(_seen["trace"], _seen["spans"], _seen["sync"], steps)}
    line = json.dumps(out)
    print("SPLIT " + line, file=sys.stderr, flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
