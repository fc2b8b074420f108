"""Executor seconds in WRITEBACK_GRAD per window step: the f32 layer
gradient's device-to-host copy and its hand-off to the optimizer; it
also absorbs the device time of the queued backward."""


def read(rec):
    return rec["op_seconds"].get("WRITEBACK_GRAD", 0.0) \
        / rec["window"]["steps"]
