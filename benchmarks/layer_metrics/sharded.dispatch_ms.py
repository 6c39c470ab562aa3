"""A sharded batch from the reader lease until the collective program's
answers are ready and the exclusion words are dropped again: the puts of
the query rows and the words (`sharded.put_ms` reads that child alone), the
program, the words' release: the span `sharded.dispatch`, total over count,
over the measured window."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "sharded.dispatch")
