"""How long a query's closed batch waits for an in-flight slot and a pool
thread: the span `batch.slot_wait` (closed -> `_run_group` starts), the
third of the three hand-offs that sum to `batch.queue_wait`; mean over the
measured window's queries."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "batch.slot_wait")
