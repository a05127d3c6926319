"""setup_s: seconds from process start to the window's start (weights,
warm-up, compiles and cache loads)."""


def read(record, trace):
    return record["setup_s"]
