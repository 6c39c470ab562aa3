"""Host time a batch spends INSIDE its device programs' calls (argument
transfer and dispatch): the seconds of the span `device.launch`, which the
device profiler's wrapper opens around every instrumented program's call,
over the measured window, over the window's batches (`batch.predict` count;
a batch that runs two programs counts both)."""

from benchmarks.serve_split_metrics import per_batch_ms


def read(reading):
    return per_batch_ms(reading, "device.launch")
