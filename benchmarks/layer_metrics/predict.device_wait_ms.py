"""What the host waits for a batch's device pass, from the call until
scores and items are host arrays: the span `als.predict.device`, total over
count, over the measured window."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "als.predict.device")
