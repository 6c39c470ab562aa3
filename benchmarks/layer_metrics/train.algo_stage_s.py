"""Seconds a job spends in the DASE train stage (`ALSAlgorithm.train`: host
prep, staging, densify, the train program, the copy back), as the program's
own stage span records it in EngineInstance.env["stage_timings"]; mean over
the window's jobs."""


def read(reading):
    stages = [j["stage_timings"].get("train") for j in reading.window.get("jobs", [])]
    stages = [s for s in stages if s is not None]
    if not stages:
        return None
    return sum(stages) / len(stages)
