"""What is left of a batch once its answers are back: the span `batch.finish`
(on the dispatcher's worker: batch_predict returned -> the batch's last future
is set, its slot about to be let go), one a batch, with `batch_size`; mean
over the measured window's batches. It is the stretch the NEXT batch no
longer waits for: the dispatcher closes the assembling batch when the one
ahead has its answers, not when its worker retires. A program that closes
on retirement has no such span and reads as None."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "batch.finish")
