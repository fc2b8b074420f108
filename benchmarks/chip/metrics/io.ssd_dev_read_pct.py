"""Share of the SSD-tier bytes read in the window that reached the block
device (``/proc/self/io`` read_bytes over the metered ssd->cpu bytes);
the rest came from the page cache. Nothing when no SSD-tier byte was
read."""


def read(rec):
    metered = sum(v for k, v in rec["traffic"].items()
                  if k.endswith(":ssd->cpu"))
    if not metered:
        return None
    return 100.0 * rec["proc_io"].get("read_bytes", 0) / metered
