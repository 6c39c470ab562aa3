"""Share of the measured window in which the dispatcher held no query
(none submitted and not yet answered): the seconds of the span
`dispatch.no_work` that fall in the window, over the window. It follows the
offered rate, not the program: the part of an idle chip no perf change can
win."""

from benchmarks.serve_split_metrics import no_work_pct as read  # noqa: F401
