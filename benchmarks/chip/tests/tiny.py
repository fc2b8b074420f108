"""A tiny benchmark directory for CPU tests: a 2-layer model at small
widths under the same harness, found by name like the real cells."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]

CONFIG = {
    "name": "tiny", "source": "test", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 250, "table_rows": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "mlp": "gelu_tanh", "param_dtype": "bfloat16"}

TRAFFIC = {
    "schedule": "vertical", "alpha": 0.25, "activation_policy": "recompute",
    "micro_batches": 2, "micro_batch": 1, "seq_len": 32, "lr": 1e-3,
    "setup_steps": 2,
    "ratios": {"ckpt": 0.0, "param": 0.5, "opt": 1.0, "act": 0.0}}

LIMITS = {"loss_gap": 1.5e-3, "grad_gap": 1e-2, "change_gap": 0.15,
          "bytes_mismatch": 0}


def make(root: Path, configs=(("tiny", CONFIG),), limits=LIMITS,
         cells=(("tiny.ssd", "tiny", "ssd"),)) -> Path:
    """Lay out a benchmark directory under ``root``; return the path of
    its BENCHMARK.json."""
    root = Path(root)
    for sub in ("configs", "traffic", "workloads"):
        (root / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "models"):
        shutil.copytree(CHIP / sub, root / sub, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    peaks = json.loads((CHIP / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "peaks.json").write_text(json.dumps(peaks))
    for name, c in configs:
        (root / "configs" / f"{name}.json").write_text(json.dumps(c))
    (root / "traffic" / "ssd.json").write_text(json.dumps(TRAFFIC))
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    bench["workloads"] = []
    for cell, cfg, traffic in cells:
        (root / "workloads" / f"{cell}.json").write_text(
            json.dumps({"limits": limits}))
        bench["workloads"].append({"name": cell, "config": cfg,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [c for c, *_ in cells]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path
