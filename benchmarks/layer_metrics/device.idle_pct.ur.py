"""Share of the Universal Recommender cell's measured window in which no
operation ran on the device (1 - union of the trace's "XLA Ops" intervals),
over the part of the window the device trace covers: the profiler keeps a
bounded number of device events, and this cell's scoring program leaves one
a posting window."""

from benchmarks.ur_metrics import idle_pct as read  # noqa: F401
