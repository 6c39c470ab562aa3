"""Seconds a job waits for its COO arrays and degree vectors to reach the
device (`device_put` until ready): the span `als.stage.transfer`, from
EngineInstance.env["stage_timings"]; mean over the window's jobs."""

from benchmarks.span_metrics import job_seconds


def read(reading):
    return job_seconds(reading, "als.stage.transfer")
