"""Seconds of a job that no span names: the driver's wall time around
`run_train` minus the job's root span, plus the part of the root span that
none of its leaf spans covers; mean over the window's jobs."""

from benchmarks.span_metrics import job_unattributed_seconds


def read(reading):
    return job_unattributed_seconds(reading)
