"""Share of the train cell's measured window in which no operation ran on
the device (1 - union of the trace's "XLA Ops" intervals over the window)."""

from benchmarks.trace_reduce import idle_pct as read  # noqa: F401
