"""Host time a batch spends blocked until its device programs' results are
ready: the seconds of the span `device.wait` (the device profiler's wrapper
around `jax.block_until_ready` of every instrumented program's output) over
the measured window, over the window's batches (`batch.predict` count)."""

from benchmarks.serve_split_metrics import per_batch_ms


def read(reading):
    return per_batch_ms(reading, "device.wait")
