"""Host time of a batch before its device pass (user lookups, exclusion
lists, padding to the bucket): the span `als.predict.prepare`, total over
count, over the measured window."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "als.predict.prepare")
