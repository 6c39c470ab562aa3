"""The fused score + top-k kernel's share of its (memory) roofline in the
steady cell; moves `query_p50_ms`."""

from benchmarks.serving_metrics import fused_recommend_roofline_pct as read  # noqa: F401
