"""Host time of a batch after its device pass (`item_vocab.inverse()` and
the `ItemScore` loop): the span `als.predict.decode`, total over count, over
the measured window."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "als.predict.decode")
