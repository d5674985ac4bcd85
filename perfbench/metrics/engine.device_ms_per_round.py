"""Device busy time in the traced window per engine round executed in
it, in milliseconds."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["rounds"] or not tr["busy_s"]:
        return None
    return 1e3 * tr["busy_s"] / tr["rounds"]
