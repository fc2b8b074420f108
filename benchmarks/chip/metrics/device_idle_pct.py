"""Share of the traced window in which no operation ran on the device
(``trace_reduce.py``); nothing without a trace."""


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
