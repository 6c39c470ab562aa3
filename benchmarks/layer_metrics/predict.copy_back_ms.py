"""Host time a batch spends copying its answers back, after the programs
are done: the spans `als.predict.copy_back` (one chip), `sharded.copy_back`
(the sharded tier) and `ur.predict.copy_back` (the Universal Recommender),
whichever the cell has, over the window's batches (`batch.predict` count)."""

from benchmarks.serve_split_metrics import per_batch_ms


def read(reading):
    return per_batch_ms(reading, "als.predict.copy_back", "sharded.copy_back",
                        "ur.predict.copy_back")
