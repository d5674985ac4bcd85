"""memory_stats()["peak_bytes_in_use"] after the window, on the fullest
chip."""


def read(rec):
    return rec["memory"]["peak_bytes"] or None
