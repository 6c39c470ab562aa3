"""The reply's way back inside the process: the span `query.wake`, from the
dispatcher's worker setting the query's future to the handler thread running
again (a thread hand-off under the interpreter lock); mean over the measured
window's queries."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "query.wake")
