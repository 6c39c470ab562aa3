"""Seconds a job waits for `ops.densify` to build the dense matrix on the
device: the span `als.stage.densify` (dispatch until ready), from
EngineInstance.env["stage_timings"]; mean over the window's jobs."""

from benchmarks.span_metrics import job_seconds


def read(reading):
    return job_seconds(reading, "als.stage.densify")
