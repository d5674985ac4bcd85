"""Process start to the window's opening: config load, build, program
load (or compile), init_state and the warm-up advance."""


def read(rec):
    return rec["setup"]["total_s"]
