"""The exclusion + top-k tail's share of its (memory) roofline: the totals
read once a batch over the `fused_masked_topk` call's device time; moves
`query_p50_ms`."""

from benchmarks.ur_metrics import fused_topk_roofline_pct as read  # noqa: F401
