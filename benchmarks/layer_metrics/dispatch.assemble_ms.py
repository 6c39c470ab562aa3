"""How long a query waits, once taken, for its batch to close: the span
`batch.assemble` (taken -> closed; its attr `closed_by` names the branch of
the drain policy that closed the batch), the second of the three hand-offs
that sum to `batch.queue_wait`; mean over the measured window's queries. A
program without `batch.pickup` has the span's name under its older meaning
(first arrival -> dispatch) and reads as None."""

from benchmarks.span_metrics import mean_ms, window_stats


def read(reading):
    if window_stats(reading, "batch.pickup") is None:
        return None
    return mean_ms(reading, "batch.assemble")
