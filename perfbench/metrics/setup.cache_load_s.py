"""Seconds the AOT compile cache spent loading the cell's programs, as
its own report gives them; nothing on a run that compiled instead."""


def read(rec):
    cache = rec["setup"].get("cache") or {}
    if not cache.get("hits") or cache.get("misses"):
        return None
    return cache["load_s"]
