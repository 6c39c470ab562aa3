"""Seconds of a job in the one sort of its pair keys (`key.sort()` in
`models/als.py` `_group_unique_pairs`): the span `als.train.pair_sort`,
from EngineInstance.env["stage_timings"]; mean over the window's jobs. Its
neighbours `als.train.pair_key` (the key's build) and `als.train.pair_group`
(the pass out of the sorted keys) are in the same table."""

from benchmarks.span_metrics import job_seconds


def read(reading):
    return job_seconds(reading, "als.train.pair_sort")
