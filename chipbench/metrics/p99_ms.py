"""p99_ms: 99th percentile of the latency of every request due in the
window, from its scheduled send to its result (client side)."""
import numpy as np


def read(record):
    if record["kind"] != "serve":
        return None
    return float(np.percentile(record["latency_s"], 99)) * 1e3
