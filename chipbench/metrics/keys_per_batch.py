"""keys_per_batch (engine): distinct keys among the lanes of a flushed
batch, the mean over the batches the engine flushed in the window
(EngineStats ``batch_keys`` over ``batches``)."""


def read(record):
    eng = record.get("engine") or {}
    if record["kind"] != "serve" or not eng.get("batches") \
            or "batch_keys" not in eng:
        return None
    return eng["batch_keys"] / eng["batches"]
