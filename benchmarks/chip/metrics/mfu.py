"""The whole step's share of the chip's bf16 peak: model FLOPs per token
(``flops.py``) times the window's tokens per second, over the peak of
the run's ``device_kind`` in ``peaks.json``."""


def read(rec):
    w = rec["window"]
    rate = w["steps"] * rec["tokens_per_step"] / w["seconds"]
    return 100.0 * rec["flops_per_token"] * rate \
        / (rec["peaks"]["bf16_flops_per_s"] * rec["chips"])
