"""The whole sharded serving step's share of the four chips' bf16 peak,
counted from the configuration (2 * 128 * 19.7 M operations a live query)
over the device time of every program on the first device x the chips, which
run the same programs in step. Moves `query_p50_ms`."""

from benchmarks.sharded_metrics import step_mfu_pct as read  # noqa: F401
