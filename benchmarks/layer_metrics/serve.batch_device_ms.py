"""Device time of one serving batch: the runs of the serving program in the
measured window (trace, "XLA Modules" line), median. A per-piece median is
what a layer metric may be; the end-to-end tails are over all queries."""

import statistics

PROGRAM = "_serve_recommend_jit"


def read(reading):
    trace = reading.trace
    if trace is None or not trace.program_runs.get(PROGRAM):
        return None
    return 1000.0 * statistics.median(trace.program_runs[PROGRAM])
