"""Host time of a batch after its device pass (item rows back to ids, the
positive scores kept): the span `ur.predict.decode`, total over count, over
the measured window."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "ur.predict.decode")
