"""Seconds of a Universal Recommender train job in each indicator's events
binarised and grouped by user on the host (one sort of packed keys,
`models/cco.py` `group_by_user`): the span `ur.train.group`, summed over the
job in EngineInstance.env["stage_timings"]; mean over the window's jobs. A
program without the span reads None."""

from benchmarks.span_metrics import job_seconds


def read(reading):
    return job_seconds(reading, "ur.train.group")
