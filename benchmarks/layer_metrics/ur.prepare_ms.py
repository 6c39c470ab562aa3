"""Host time of a batch before its device pass — the history read (its own
span and metric, most of this one), the history arrays padded to the bucket,
the exclusion in the form the batch's ids call for: the span
`ur.predict.prepare`, total over count, over the measured window."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "ur.predict.prepare")
