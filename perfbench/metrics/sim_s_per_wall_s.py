"""Simulated seconds advanced in the window per wall second of it (the
window ends in a device sync)."""


def read(rec):
    w = rec["window"]
    return w["sim_s"] / w["wall_s"]
