"""Wall time of one batch's `algo.batch_predict` loop on the dispatcher's
worker thread: the span `batch.predict`, total over count, over the
measured window."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "batch.predict")
