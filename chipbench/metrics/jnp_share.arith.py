"""jnp_share.arith (core compositions): device time outside Pallas
kernels over device busy time in the traced window, in %."""


def read(record):
    tr = record.get("trace")
    if record["kind"] != "arith" or not tr or not tr["busy_s"]:
        return None
    return 100.0 * tr["outside_kernels_s"] / tr["busy_s"]
