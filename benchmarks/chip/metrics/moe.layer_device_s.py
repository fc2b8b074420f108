"""Device seconds per window step in the MoE layers' jits: the traced
window's modules whose jit name ends in the kind label ``_moe``
(``layer_fwd_moe``, ``layer_fwd_res_moe``, ``layer_bwd_res_moe``), from
``trace_reduce.py``'s per-module device seconds. Nothing without a trace
or without such a module."""

SUFFIX = "_moe"


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    secs = [s for name, s in t["device_ops"] if name.endswith(SUFFIX)]
    if not secs:
        return None
    return sum(secs) / rec["window"]["steps"]
