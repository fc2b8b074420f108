"""The runtime's peak of device memory in use, read after the window."""


def read(rec):
    peak = rec["device"].get("memory_peak_bytes")
    return None if peak is None else peak / 2**30
