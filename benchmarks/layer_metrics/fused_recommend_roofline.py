"""The fused score + top-k kernel's share of its (memory) roofline in the
saturated cell; moves `query_qps`."""

from benchmarks.serving_metrics import fused_recommend_roofline_pct as read  # noqa: F401
