"""Seconds per window step the executor waits on the alpha-delayed tail
of the host Adam (the plan's ``opt_wait`` phase)."""


def read(rec):
    return rec["phase_time"].get("opt_wait", 0.0) / rec["window"]["steps"]
