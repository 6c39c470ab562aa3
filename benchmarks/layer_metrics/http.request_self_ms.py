"""What a request spends in the HTTP handler outside its decode, wait and
encode spans and the dispatcher's spans: the SELF time of the span
`server.request`, over count, over the measured window."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "server.request", field="self_s")
