"""Seconds a job waits for the whole-train program, from dispatch until
the factors are ready on the device: the span `als.train.program`, from
EngineInstance.env["stage_timings"]; mean over the window's jobs."""

from benchmarks.span_metrics import job_seconds


def read(reading):
    return job_seconds(reading, "als.train.program")
