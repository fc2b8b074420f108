"""Crash-consistent training checkpoints for the offload engines.

The checkpoint is the engine's FULL trainable state — per layer the
low-precision params and the f32 master/m/v optimizer vectors, plus the
device-resident embedding/head tensors, their Adam state, and
``step_num`` — exactly the state whose round-trip the plan-swap
bitwise pin established (``tests/test_autotune.py`` grew it ad hoc;
this module is its promotion). Vectors are stored ASSEMBLED (full
``P``-element vectors, not rank shards), so a checkpoint written by the
single-rank engine restores into the DP engine and vice versa: DP
sharding is contiguous (``shard_bounds``), so assembly is
concatenation and restore is slicing — both bitwise.

Crash consistency is manifest-journaled:

* every tensor is written to its own generation-stamped file
  (``<name>.g<step>.bin``, fsynced) with its CRC32C recorded;
* the manifest (``manifest.json`` — version, engine meta, per-tensor
  file/nbytes/dtype/shape/crc) is written LAST via temp + rename +
  fsync: the checkpoint EXISTS only once the manifest commits, and a
  crash mid-save leaves the previous manifest pointing at the previous
  generation's files, which are garbage-collected only AFTER the new
  manifest is durable;
* restore reads and CRC-verifies every tensor BEFORE mutating any
  engine state (all-or-nothing): a torn manifest, a missing/short/
  corrupt tensor file, or meta that doesn't match the engine (L, P,
  param dtype) raises :class:`CheckpointError` and leaves the engine
  exactly as it was.

Restore quiesces first (``finish()`` + the same coordinator
clear as the plan-swap seam) so no in-flight spill or armed α gate can
interleave with the state writes, then writes through
``TieredVector.write_full`` — unmetered, like initialization, so a
restore perturbs no traffic accounting.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterator, Tuple

import numpy as np

from repro.io.integrity import crc32c

CKPT_VERSION = 1
MANIFEST = "manifest.json"


class CheckpointError(IOError):
    """The checkpoint is unusable — torn/missing manifest, corrupt or
    missing tensor bytes, or meta that doesn't match the engine. Raised
    BEFORE any engine state is mutated."""


def _fname(name: str, gen: int) -> str:
    return name.replace(":", "_").replace("/", "_") + f".g{gen}.bin"


def _is_dp(eng) -> bool:
    return hasattr(eng, "ranks")


def _assemble(eng, attr: str, l: int, dtype) -> np.ndarray:
    """Layer ``l``'s full vector from ``attr`` (``p_vecs``/``m_master``/
    ``m_m``/``m_v``), concatenating rank shards on the DP engine."""
    if _is_dp(eng):
        out = np.empty(eng.P, dtype)
        for rk, (lo, hi) in zip(eng.ranks, eng.bounds):
            out[lo:hi] = getattr(rk, attr)[l].read()
        return out
    return np.asarray(getattr(eng, attr)[l].read(), dtype).copy()


def _p_meta(eng):
    """The layer size the manifest records: ``P`` for a stack whose
    layers are alike, else the list of per-layer sizes."""
    return int(eng.P) if eng.P is not None else [int(n) for n in
                                                 eng.layer_P]


_VEC_ATTRS = (("p", "p_vecs"), ("master", "m_master"),
              ("m", "m_m"), ("v", "m_v"))
_HEAD_TENSORS = ("embed", "unembed", "final_norm")


def _state_items(eng) -> Iterator[Tuple[str, np.ndarray]]:
    pdt = np.dtype(eng.ocfg.param_dtype)
    for l in range(eng.L):
        for key, attr in _VEC_ATTRS:
            dt = pdt if key == "p" else np.float32
            yield f"{key}:{l}", _assemble(eng, attr, l, dt)
    for t in _HEAD_TENSORS:
        yield t, np.asarray(eng.__dict__[t])
        for k in ("m", "v"):
            yield f"head:{t}:{k}", np.asarray(eng.head_state[t][k])


def _expected_names(L: int):
    names = {f"{key}:{l}" for key, _ in _VEC_ATTRS for l in range(L)}
    for t in _HEAD_TENSORS:
        names.add(t)
        names.update({f"head:{t}:m", f"head:{t}:v"})
    return names


def _quiesce(eng):
    """Drain every stream and drop per-plan residue — the plan-swap
    seam's contract, so restored state can't race in-flight I/O.
    ``finish()`` is best-effort: when restoring after a FAILED step its
    flushes may re-raise that step's fault, but the restore is about to
    overwrite all state anyway — the coordinator clears below make the
    engine quiet regardless."""
    try:
        eng.finish()
    except Exception:
        pass
    stacks = eng.ranks if _is_dp(eng) else (eng,)
    for s in stacks:
        s.params_c.reset()
        s.params_c.clear_gates()
        s.ckpt_c.clear()
        s.act_c.clear()
        s.opt_c.clear()


def save_checkpoint(eng, directory: str) -> str:
    """Write a crash-consistent checkpoint of ``eng`` into ``directory``
    and return the committed manifest path. Non-destructive: training
    can continue on the same engine afterwards."""
    eng.finish()            # α tails flushed => vectors are authoritative
    os.makedirs(directory, exist_ok=True)
    gen = int(eng.step_num)
    tensors: Dict[str, dict] = {}
    for name, arr in _state_items(eng):
        arr = np.ascontiguousarray(arr)
        data = arr.tobytes()
        fn = _fname(name, gen)
        with open(os.path.join(directory, fn), "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        tensors[name] = {"file": fn, "nbytes": len(data),
                         "dtype": str(arr.dtype),
                         "shape": list(arr.shape),
                         "crc32c": crc32c(data)}
    doc = {"version": CKPT_VERSION,
           "meta": {"L": int(eng.L), "P": _p_meta(eng), "step_num": gen,
                    "param_dtype": str(np.dtype(eng.ocfg.param_dtype)),
                    "arch": getattr(eng.cfg, "name", ""),
                    "ranks": int(getattr(eng, "R", 1))},
           "tensors": tensors}
    target = os.path.join(directory, MANIFEST)
    tmp = target + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, target)
    # only now — with the new manifest durable — drop files the
    # previous generation's manifest referenced
    keep = {spec["file"] for spec in tensors.values()}
    for fn in os.listdir(directory):
        if fn.endswith(".bin") and fn not in keep:
            try:
                os.unlink(os.path.join(directory, fn))
            except FileNotFoundError:
                pass
    return target


def load_manifest(directory: str) -> dict:
    """Parse and structurally validate the committed manifest (no
    tensor reads). Raises :class:`CheckpointError` on a missing, torn,
    or wrong-version manifest."""
    mp = os.path.join(directory, MANIFEST)
    try:
        with open(mp) as f:
            doc = json.load(f)
    except FileNotFoundError:
        raise CheckpointError(f"no checkpoint manifest at {mp}")
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise CheckpointError(
            f"torn or corrupt checkpoint manifest at {mp}: {e}")
    if doc.get("version") != CKPT_VERSION:
        raise CheckpointError(
            f"checkpoint manifest version {doc.get('version')!r} != "
            f"{CKPT_VERSION}")
    if not isinstance(doc.get("tensors"), dict) \
            or not isinstance(doc.get("meta"), dict):
        raise CheckpointError(
            f"checkpoint manifest at {mp} is structurally invalid")
    return doc


def restore_checkpoint(eng, directory: str) -> int:
    """Restore ``eng`` from the checkpoint in ``directory`` and return
    the restored ``step_num``. All tensor bytes are read and
    CRC-verified before any engine state is touched; the restored
    trajectory is bitwise (f32) — the plan-swap pin, now through disk.
    """
    doc = load_manifest(directory)
    meta = doc["meta"]
    pdt = str(np.dtype(eng.ocfg.param_dtype))
    for key, have in (("L", int(eng.L)), ("P", _p_meta(eng)),
                      ("param_dtype", pdt)):
        if meta.get(key) != have:
            raise CheckpointError(
                f"checkpoint meta mismatch: {key}={meta.get(key)!r} "
                f"but this engine has {key}={have!r}")
    missing = _expected_names(eng.L) - set(doc["tensors"])
    if missing:
        raise CheckpointError(
            f"checkpoint is missing tensors: {sorted(missing)[:4]}...")
    arrays: Dict[str, np.ndarray] = {}
    for name, spec in doc["tensors"].items():
        fp = os.path.join(directory, spec["file"])
        try:
            with open(fp, "rb") as f:
                data = f.read()
        except FileNotFoundError:
            raise CheckpointError(
                f"checkpoint tensor file missing: {fp}")
        if len(data) != int(spec["nbytes"]):
            raise CheckpointError(
                f"torn checkpoint tensor {name!r}: {len(data)}/"
                f"{spec['nbytes']} bytes")
        if crc32c(data) != int(spec["crc32c"]):
            raise CheckpointError(
                f"corrupt checkpoint tensor {name!r}: CRC32C mismatch")
        arrays[name] = np.frombuffer(
            data, dtype=np.dtype(spec["dtype"])).reshape(
                spec["shape"]).copy()
    # everything verified — now (and only now) mutate the engine
    import jax.numpy as jnp
    _quiesce(eng)
    dp = _is_dp(eng)
    for l in range(eng.L):
        for key, attr in _VEC_ATTRS:
            arr = arrays[f"{key}:{l}"]
            if dp:
                for rk, (lo, hi) in zip(eng.ranks, eng.bounds):
                    getattr(rk, attr)[l].write_full(arr[lo:hi])
            else:
                getattr(eng, attr)[l].write_full(arr)
    for t in _HEAD_TENSORS:
        setattr(eng, t, jnp.asarray(arrays[t]))
    eng.head_state = {t: {k: jnp.asarray(arrays[f"head:{t}:{k}"])
                          for k in ("m", "v")}
                      for t in _HEAD_TENSORS}
    eng.step_num = int(meta["step_num"])
    return eng.step_num
