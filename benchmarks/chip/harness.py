"""What the benchmark finds by name, and the rules every cell shares.

Everything that belongs to one configuration, one cell or one metric sits
in a file of its own under this directory, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   the model's sizes as run, with its source;
* ``models/<model>.py``       what the benchmark knows of a model family's
                              layers: its sizes, leaves, initial weights,
                              plain equations, FLOP count and the
                              program's configuration; a configuration
                              names it under ``"model"`` (default
                              ``dense``);
* ``traffic/<traffic>.json``  batch, sequence, schedule, tiers, steps:
                              what the data generator and the engine read;
* ``workloads/<cell>.json``   the cell's limits for ``correct``;
* ``metrics/<metric>.py``     ``read(record) -> float | None``;
* ``peaks.json``              published peaks, keyed by ``device_kind``.

Adding a configuration, a model family, a cell or a metric is adding a
file and its entry in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: the model module of a configuration file without a ``"model"`` key
DEFAULT_MODEL = "dense"


def load_module(path: Path, name: str) -> ModuleType:
    """The Python file at ``path``, imported under ``name`` (and entered
    in ``sys.modules``, where dataclasses look their module up)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Registry:
    """Name lookups rooted at one benchmark directory."""

    def __init__(self, here: Path = HERE, benchmark: Optional[Path] = None):
        self.here = Path(here)
        path = benchmark or ROOT / "BENCHMARK.json"
        self.bench = json.loads(Path(path).read_text())
        self._models: Dict[str, ModuleType] = {}

    def _json(self, sub: str, name: str) -> dict:
        path = self.here / sub / f"{name}.json"
        if not path.is_file():
            raise LookupError(f"no {sub} file named {name!r} ({path})")
        return json.loads(path.read_text())

    def cell(self, name: str) -> dict:
        """The cell's ``BENCHMARK.json`` entry merged with its traffic
        mix and its own file."""
        entry = next((w for w in self.bench["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise LookupError(f"BENCHMARK.json lists no workload {name!r}")
        return {**self._json("traffic", entry["traffic"]),
                **self._json("workloads", name), **entry}

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def model(self, name: str) -> ModuleType:
        """``models/<name>.py``, loaded once. The harness uses only this
        of it:

        * ``Arch.from_config(c)``: the sizes the reference needs; every
          ``Arch`` has ``d``, ``vocab`` (table rows), ``layers`` and
          ``eps`` (the final norm's), which the shared head reads;
        * ``layer_leaves(a, l)``: (name, shape) of layer ``l``'s tensors
          in the order of the program's flat per-layer vector;
        * ``layer_kind(a, l)``: a hashable key; layers of one kind share
          the reference's compiled forward and backward, so the kind
          carries all that sets a layer's equations apart;
        * ``init_params(a, key)``: the initial weights as the program
          draws them, keyed by layer index and ``"head"``;
        * ``block(a, mode, p, x, kind)``: the plain equations of a layer
          of that kind, with ``reference.py``'s operand rounding for
          ``mode``;
        * ``flops_per_token(c, seq_len)``: ``flops.per_token`` of the
          configuration's own counts;
        * ``arch_config(c)``: the program's ``ArchConfig``, the one
          function that imports the program under test.
        """
        if name not in self._models:
            path = self.here / "models" / f"{name}.py"
            if not path.is_file():
                raise LookupError(f"no model module named {name!r} ({path})")
            self._models[name] = load_module(
                path, f"chip_model_{name.replace('.', '_')}")
        return self._models[name]

    def model_of(self, cfg: dict) -> ModuleType:
        """The model module a configuration file names."""
        return self.model(cfg.get("model", DEFAULT_MODEL))

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.here / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise LookupError(
                f"device_kind {device_kind!r} is not in peaks.json "
                f"(known: {sorted(table['devices'])})")
        return table["devices"][device_kind]

    def metrics(self, cell: str, trace: bool) -> List[dict]:
        """The metric entries a run of ``cell`` reports: the end-to-end
        ones without a trace, the per-layer ones with it."""
        group = self.bench["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        path = self.here / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise LookupError(f"no reader for metric {metric!r} ({path})")
        return load_module(
            path, f"chip_metric_{metric.replace('.', '_')}").read


def read_metrics(reg: Registry, cell: str, trace: bool,
                 record: dict) -> Dict[str, dict]:
    """Every metric of the run that its reader finds something for."""
    out = {}
    for m in reg.metrics(cell, trace):
        value = reg.reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_window(step: Callable[[int], object], seconds: float,
               clock: Callable[[], float] = time.perf_counter):
    """Run ``step(i)`` back to back from the window's start; the window
    closes at the end of the first step that finishes at or after
    ``seconds``. Returns (start, end, per-step seconds, step results)."""
    t0 = clock()
    times, results = [], []
    end = t0
    while True:
        a = clock()
        results.append(step(len(times)))
        end = clock()
        times.append(end - a)
        if end - t0 >= seconds:
            return t0, end, times, results


class CompileClock:
    """The number of backend compiles, from JAX's own monitoring events."""

    def __init__(self):
        self.compiles = 0

    def __call__(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def proc_io() -> Dict[str, int]:
    """This process's block-device counters (``/proc/self/io``)."""
    out = {}
    with open("/proc/self/io") as f:
        for line in f:
            k, v = line.split(":")
            out[k.strip()] = int(v)
    return out


def delta(after: Dict[str, float], before: Dict[str, float]) -> dict:
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(after) | set(before)}
