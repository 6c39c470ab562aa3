"""Self time of the all-reduce (the query block's gather by `psum`) and
all-gather (the candidates' merge) operations, a sharded batch, on the first
device (trace, "XLA Ops" line); a collective's time holds its wait for the
slowest shard, which is why one plane stands for all."""

from benchmarks.sharded_metrics import collective_ms as read  # noqa: F401
