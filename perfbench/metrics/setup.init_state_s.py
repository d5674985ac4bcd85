"""Host clock around engine.init_state to block_until_ready."""


def read(rec):
    return rec["setup"]["init_state_s"]
