"""ntt_us_per_op (kernels): device time of the NTT multiply kernel
(``ntt_mul``, one launch per CRT prime) per product completed in the
traced window, in microseconds.

The trace names the kernel after its jitted wrapper, ``_call`` with
four operands (both operands and the forward and inverse twiddles); so
the metric reads only where the multiply dispatcher chose the ``ntt``
tier, and nothing else, in set-up.  No such choice, or no events of the
kernel: nothing to read, which fails a run that lists this metric."""

KERNEL = "_call/4"              # ntt_mul ops._call: a, b, wf, wi
DISPATCH = ("mul", ["ntt"])


def read(record):
    dispatcher, choices = DISPATCH
    if record.get("dispatch", {}).get(dispatcher) != choices:
        return None
    seconds = record.get("trace", {}).get("kernels", {}).get(KERNEL)
    if not seconds or not record["trace"]["ops"]:
        return None
    return 1e6 * seconds / record["trace"]["ops"]
