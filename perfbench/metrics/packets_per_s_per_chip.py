"""Packets routed in the window (the sum of the device's per-host send
counters, read at its two ends) per wall second, per chip."""


def read(rec):
    w = rec["window"]
    return w["packets"] / w["wall_s"] / rec["chips"]
