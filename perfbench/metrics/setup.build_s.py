"""Host clock around config load and the Controller build (columnar
host plane, topology tables, DeviceRunner and its engine)."""


def read(rec):
    return rec["setup"]["build_s"]
