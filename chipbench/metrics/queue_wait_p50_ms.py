"""queue_wait_p50_ms (engine): median time from a request's scheduled send
to the start of the engine call that flushed it (the benchmark's stamps
around engine calls)."""
import numpy as np


def read(record):
    if record["kind"] != "serve" or not record["queue_wait_s"]:
        return None
    return float(np.percentile(record["queue_wait_s"], 50)) * 1e3
