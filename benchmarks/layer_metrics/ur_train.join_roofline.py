"""The join program's share of its (memory) roofline: the least bytes a
job's join programs must move — each pair read once and written once sorted
at 8 B, a count written once a distinct pair, all counted by the driver from
its own downsampled events — over the device time of `_pair_counts_jit`'s
runs in the traced window, at 819 GB/s; moves `train_job_s`. The pairs are
expanded from the kept events on the host, outside the timed program, so
the events are not counted."""

from benchmarks.ur_train_metrics import join_roofline_pct as read  # noqa: F401
