"""Share of the sharded serving cell's measured window in which no operation
ran on a device (1 - union of the trace's "XLA Ops" intervals over the
window, `busy_s` averaged over the four device planes)."""

from benchmarks.trace_reduce import idle_pct as read  # noqa: F401
