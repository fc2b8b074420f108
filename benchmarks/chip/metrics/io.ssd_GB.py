"""SSD-tier bytes per step, both directions, in GB: the meters over the
whole run, once every tail has landed, over its steps. The run's
reconciliation holds them equal to the plan's prediction, byte for byte.
Nothing when the cell keeps no tier on SSD."""


def read(rec):
    n = sum(v for k, v in rec["traffic_per_step"].items()
            if k.endswith((":cpu->ssd", ":ssd->cpu")))
    return n / 1e9 if n else None
