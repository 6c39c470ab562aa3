"""Host seconds of a job before anything is sent to the device: the spans
`als.train.degrees` (the two `np.add.at` passes), `als.train.dense_eligible`
(`np.unique` over the pair keys, the byte gate) and `als.stage.host_prep`
(casts, `int8_scale`, padding), from EngineInstance.env["stage_timings"];
mean over the window's jobs."""

from benchmarks.span_metrics import job_seconds


def read(reading):
    return job_seconds(
        reading, "als.train.degrees", "als.train.dense_eligible",
        "als.stage.host_prep")
