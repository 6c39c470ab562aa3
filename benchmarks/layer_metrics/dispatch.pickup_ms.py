"""How long a query lies in the dispatcher's queue before the loop thread
takes it: the span `batch.pickup` (put -> taken), the first of the three
hand-offs that sum to `batch.queue_wait`; mean over the measured window's
queries."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "batch.pickup")
