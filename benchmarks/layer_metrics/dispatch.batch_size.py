"""Queries per coalesced device batch: the dispatcher's own `batch_size`
histogram (server registry), sum over count, over the measured window."""


def read(reading):
    batches = reading.window.get("batches", 0)
    if not batches:
        return None
    return reading.window["batched_queries"] / batches
