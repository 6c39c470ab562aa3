"""Seconds of a Universal Recommender train job in Mahout's downsampling, drawn
from the stated hash (`models/cco.py` `downsample`): the span
`ur.train.downsample`, summed over the job in
EngineInstance.env["stage_timings"]; mean over the window's jobs. A program
without the span reads None."""

from benchmarks.span_metrics import job_seconds


def read(reading):
    return job_seconds(reading, "ur.train.downsample")
