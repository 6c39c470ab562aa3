"""Host time of a batch's live history read — one `find_by_entities` round
trip an indicator and the mapping of every event's target to its row: the span
`ur.history_read` (inside `ur.predict.prepare`), total over count, over the
measured window."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "ur.history_read")
