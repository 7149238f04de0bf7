"""setup_s: process start to the start of the window (host clock)."""


def read(record):
    return record["setup_s"]
