"""A batch's device pass as the host sees it, until scores and items are
host arrays: the span `ur.predict.device`, total over count, over the
measured window."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "ur.predict.device")
