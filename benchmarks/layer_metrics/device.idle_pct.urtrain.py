"""Share of the Universal Recommender train cell's jobs in which no
operation ran on the device: 1 - the trace's busy seconds over the seconds
of the window's `run_train` calls. The window also holds the benchmark's
read-back of each job's model (`drivers/ur_train_jobs.py` `read_back`,
numpy on the host, no device work); it is left out of the denominator, so
the share is the idle time of the jobs alone. None without a trace or a
job."""


def read(reading):
    if reading.trace is None:
        return None
    inside = sum(j["seconds"] for j in reading.window.get("jobs", []))
    if inside <= 0:
        return None
    return 100.0 * (1.0 - reading.trace.busy_s / inside)
