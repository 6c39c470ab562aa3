"""Device time of one sharded serving batch: the runs of `_sharded_recommend`
on the first device in the measured window (trace, "XLA Modules" line),
median. The program is symmetric across shards and its collectives keep them
in step, so the first plane stands for all four."""

from benchmarks.sharded_metrics import batch_device_ms as read  # noqa: F401
