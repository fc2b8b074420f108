"""Tokens of the window's whole steps over the window's seconds."""


def read(rec):
    w = rec["window"]
    return w["steps"] * rec["tokens_per_step"] / w["seconds"]
