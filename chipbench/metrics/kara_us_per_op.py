"""kara_us_per_op (kernels): device time of the fused Karatsuba kernel
(``kara_mul`` ``kara_kernel``) per product completed in the traced
window, in microseconds.

The trace names the kernel after its jitted wrapper, ``_call`` with two
operands, a name other two-operand kernels share; so the metric reads
only where the multiply dispatcher chose the ``pallas_kara`` tier, and
nothing else, in set-up.  No such choice, or no events of the kernel:
nothing to read, which fails a run that lists this metric."""

KERNEL = "_call/2"              # kara_mul ops._call: two operands
DISPATCH = ("mul", ["pallas_kara"])


def read(record):
    dispatcher, choices = DISPATCH
    if record.get("dispatch", {}).get(dispatcher) != choices:
        return None
    seconds = record.get("trace", {}).get("kernels", {}).get(KERNEL)
    if not seconds or not record["trace"]["ops"]:
        return None
    return 1e6 * seconds / record["trace"]["ops"]
