"""The whole serving step's share of the chip's bf16 peak in the steady
cell, counted from the configuration; moves `query_p50_ms`."""

from benchmarks.serving_metrics import serve_step_mfu_pct as read  # noqa: F401
