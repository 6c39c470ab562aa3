"""What a batch's device-wait span holds that no span inside it names (the
profiler's bookkeeping, padding accounting, the spans' own cost): the SELF
seconds of `als.predict.device` and `ur.predict.device`, whichever the cell
has, over the window's batches (`batch.predict` count). A program whose
device-wait span has no `device.launch` inside it keeps all of the span to
itself and reads as None."""

from benchmarks.serve_split_metrics import per_batch_ms
from benchmarks.span_metrics import window_stats


def read(reading):
    if window_stats(reading, "device.launch") is None:
        return None
    return per_batch_ms(reading, "als.predict.device", "ur.predict.device",
                        field="self_s")
