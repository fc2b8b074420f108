"""Executor seconds in FETCH_PARAM per window step: the layer's host or
SSD read and its synchronous host-to-device copy."""


def read(rec):
    return rec["op_seconds"].get("FETCH_PARAM", 0.0) / rec["window"]["steps"]
