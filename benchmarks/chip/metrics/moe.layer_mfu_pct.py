"""The MoE layers' share of the chip's bf16 peak while they run: their
model FLOPs per step (``models/deepseek_v2.py``'s
``moe_layer_flops_per_token`` by ``flops.py``'s rule, recompute not
counted, times the step's tokens) over their device seconds per step
(``moe.layer_device_s``) times the peak of the run's ``device_kind``.
The record carries no per-kind FLOPs, so this reader loads the
configuration and its model module itself. Nothing without a trace."""
import sys
from pathlib import Path

CONFIG = "deepseek-v2-lite"
CELL = "dsv2-lite.ssd.m16s2k"


def _device_s(rec):
    here = Path(__file__).resolve().parent
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_metric_moe_layer_device_s", here / "moe.layer_device_s.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def read(rec):
    secs = _device_s(rec)
    if not secs:
        return None
    chip = Path(__file__).resolve().parents[1]
    if str(chip) not in sys.path:
        sys.path.insert(0, str(chip))
    import harness
    reg = harness.Registry(chip)
    cfg = reg.config(CONFIG)
    seq = reg.cell(CELL)["seq_len"]
    per_step = reg.model_of(cfg).moe_layer_flops_per_token(cfg, seq) \
        * rec["tokens_per_step"]
    return 100.0 * per_step / (secs * rec["peaks"]["bf16_flops_per_s"]
                               * rec["chips"])
