"""1 - (union of device op intervals) / (the traced window), averaged
over the chips."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
