"""Seconds of a Universal Recommender train job in the joins: for each
indicator the primary x indicator pairs expanded on the host, handed over,
sorted by (item, thing) and counted on the device (`models/cco.py`
`join_indicator`, `_pair_counts_jit`): the span `ur.train.join`, summed over
the job in EngineInstance.env["stage_timings"]; mean over the window's jobs.
A program without the span reads None."""

from benchmarks.span_metrics import job_seconds


def read(reading):
    return job_seconds(reading, "ur.train.join")
