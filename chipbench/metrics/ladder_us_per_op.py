"""ladder_us_per_op (kernels): device time of the Montgomery ladder
kernel (``dot_modmul`` ``ladder_kernel``) over the requests completed in
the traced window, in microseconds.

The Montgomery and Barrett ladders share the name in the trace, so the
metric reads only where the modexp dispatcher chose the Montgomery
ladder (``pallas``), and nothing else, in set-up.  No such choice, or no
events of the kernel: nothing to read, which fails a run that lists this
metric."""

KERNEL = "_ladder_call/5"        # the ladder kernel inside ops._ladder_call
DISPATCH = ("modexp", ["pallas"])


def read(record):
    dispatcher, choices = DISPATCH
    if record.get("dispatch", {}).get(dispatcher) != choices:
        return None
    seconds = record.get("trace", {}).get("kernels", {}).get(KERNEL)
    if not seconds or not record["trace"]["ops"]:
        return None
    return 1e6 * seconds / record["trace"]["ops"]
