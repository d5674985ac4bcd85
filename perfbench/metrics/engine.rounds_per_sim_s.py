"""Engine rounds (conservative windows) executed per simulated second
of the window, from the program's round counter. A count: it repeats
exactly for one seed."""


def read(rec):
    w = rec["window"]
    return w["rounds"] / w["sim_s"]
