"""Share of the window's wall in which the host was not blocked on the
device: 1 - (the dispatch syncs' wall, as supervise.advance measures
it) / (the window's wall)."""


def read(rec):
    w = rec["window"]
    return 1.0 - w["sync_s"] / w["wall_s"]
