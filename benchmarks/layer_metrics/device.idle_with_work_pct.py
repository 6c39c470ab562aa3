"""Share of the measured window in which the device was idle WITH a query
in the server: the cell's own `device.idle_pct.*` reading, by that metric's
own reader, minus `dispatch.no_work_pct` — the idle a perf change can win.
Absent in an untraced run."""

from benchmarks.serve_split_metrics import idle_with_work_pct as read  # noqa: F401
