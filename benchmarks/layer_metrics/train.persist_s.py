"""Seconds of a job's persist stage (`make_serializable_models`,
`pickle.dumps`, the model store's write): the stage span `train.persist`,
under its key `persist` in EngineInstance.env["stage_timings"]; mean over
the window's jobs."""

from benchmarks.span_metrics import job_seconds


def read(reading):
    return job_seconds(reading, "persist")
