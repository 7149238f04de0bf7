"""idle_share.arith (device): 1 minus the union of the device's busy
intervals over the traced window, in %, in an arithmetic cell."""


def read(record):
    tr = record.get("trace")
    if record["kind"] != "arith" or not tr or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
