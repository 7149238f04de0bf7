"""batch_fill (engine): real lanes over the lanes of the batches the
engine flushed in the window (EngineStats), in %."""


def read(record):
    if record["kind"] != "serve" or not record["engine"]["batches"]:
        return None
    eng = record["engine"]
    return 100.0 * eng["served"] / (eng["batches"] * record["slots"])
