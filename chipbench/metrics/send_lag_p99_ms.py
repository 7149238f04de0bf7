"""send_lag_p99_ms (client): 99th percentile of how late the generator
sent a request against its schedule, from the generator's own stamps."""
import numpy as np


def read(record):
    if record["kind"] != "serve":
        return None
    return float(np.percentile(record["send_lag_s"], 99)) * 1e3
