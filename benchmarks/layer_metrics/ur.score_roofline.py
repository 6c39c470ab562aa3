"""The scoring ops' share of their (memory) roofline: the postings the
window's histories name read once (the driver's count, from the tables and
the inserted histories) and the totals written once, over the program's
device time less the tail kernel's; moves `query_p50_ms`."""

from benchmarks.ur_metrics import score_roofline_pct as read  # noqa: F401
