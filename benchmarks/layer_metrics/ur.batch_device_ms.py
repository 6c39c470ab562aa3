"""Device time of one Universal Recommender batch: the runs of the scoring
program `_score_topk_jit` and of the add-only `_accumulate_jit` in the
measured window (trace, "XLA Modules" line), over the batches."""

from benchmarks.ur_metrics import batch_device_ms as read  # noqa: F401
