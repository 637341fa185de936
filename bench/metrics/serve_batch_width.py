"""Events per micro-batch: ``ServeStats`` events processed over batches."""


def read(w):
    c = w["counters"]
    return c["serve.events_processed"] / c["serve.batches"] if c["serve.batches"] else None
