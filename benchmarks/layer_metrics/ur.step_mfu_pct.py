"""The whole Universal Recommender serving step's share of the chip's bf16
peak: 2 x indicators x correlators x items operations a live query, from the
configuration alone, over the device time of every program; moves
`query_p50_ms`."""

from benchmarks.ur_metrics import step_mfu_pct as read  # noqa: F401
