"""Megabytes of packed exclusion words built and shipped, a sharded batch
(the server registry's `sharded_exclusion_bytes_total` over
`sharded_batches_total`, over the measured window)."""

from benchmarks.sharded_metrics import exclusion_mb_per_batch as read  # noqa: F401
