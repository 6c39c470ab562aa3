"""The 99th percentile of the steady cell's query latency, over ALL queries
of the window as the end-to-end median is (a failed query counts as slower
than any reply). It stands here and not among the end-to-end metrics because
it swings: its first-to-third-quartile spread over 6 runs read 3.4 % and
7.7 % on the v5e (PR 24), too wide for any bound the contract allows. Read in
the traced run, so under the profiler's overhead."""


def read(reading):
    return reading.window.get("end_to_end", {}).get("query_p99_ms")
