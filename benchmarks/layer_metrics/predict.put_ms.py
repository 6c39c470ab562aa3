"""Host time a batch spends handing its inputs to the device before its
program can be called: the spans `als.predict.put` (one chip: the row ids,
the exclusion, a scalar), `sharded.dispatch.put` (the sharded tier: held
until the arrays are resident) and `ur.predict.put` (the zeroed total and
the batch's one packed input), whichever the cell has, over the window's
batches (`batch.predict` count)."""

from benchmarks.serve_split_metrics import per_batch_ms


def read(reading):
    return per_batch_ms(reading, "als.predict.put", "sharded.dispatch.put",
                        "ur.predict.put")
