"""The shard-local fused score + top-k kernel's share of its (memory)
roofline in the sharded cell: one shard's padded item slab streamed once a
batch over the kernel's time on the first device; every shard streams a slab
of the same size through the same kernel. Moves `query_p50_ms`."""

from benchmarks.sharded_metrics import fused_recommend_roofline_pct as read  # noqa: F401
