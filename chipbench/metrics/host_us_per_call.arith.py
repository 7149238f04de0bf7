"""host_us_per_call.arith (facade): mean host time of one ``api.mul``
call, from the call to its return, before the block on the result."""


def read(record):
    if record["kind"] != "arith":
        return None
    return 1e6 * record["host_call_s"] / record["calls"]
