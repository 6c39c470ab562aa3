"""The whole UR train's share of the chip's bf16 peak: the operations a
job needs (an add a pair and an LLR a distinct pair, from the driver's own
count of the work) over the device time of every program the traced
window's jobs ran. Sorting and counting are not arithmetic: the share reads
near 0, and says so."""

from benchmarks.ur_train_metrics import step_mfu_pct as read  # noqa: F401
