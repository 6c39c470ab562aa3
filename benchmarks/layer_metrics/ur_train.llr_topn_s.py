"""Seconds of a Universal Recommender train job in each indicator's LLR of its
runs and every item's best 50 on the device (`models/cco.py`
`_llr_topn_jit`): the span `ur.train.llr_topn`, summed over the job in
EngineInstance.env["stage_timings"]; mean over the window's jobs. A program
without the span reads None."""

from benchmarks.span_metrics import job_seconds


def read(reading):
    return job_seconds(reading, "ur.train.llr_topn")
