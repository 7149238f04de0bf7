"""ops_per_s: operations completed in the window over the window's length."""


def read(record):
    if record["kind"] != "arith":
        return None
    return record["ops_done"] / record["window_s"]
