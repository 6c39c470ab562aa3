"""What a sharded batch's puts take until the arrays are resident on the
chips: the query rows and, where the batch has a filter, the packed
exclusion words, which the host first re-lays for the column-sharded put:
the span `sharded.dispatch.put` (a child of `sharded.dispatch`), total over
count, over the measured window. The rest of `sharded.dispatch` is the
collective program and, in `sharded.dispatch.release`, the words' release."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "sharded.dispatch.put")
