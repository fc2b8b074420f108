"""Process start to window start: JAX start-up, engine construction
(which writes the initial state to its tiers), the set-up steps (the
first compiles) and the readbacks the comparison needs."""


def read(rec):
    return rec["setup_s"]
