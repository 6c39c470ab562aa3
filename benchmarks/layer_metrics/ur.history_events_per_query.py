"""Events a query's history read returned, all indicators together (at most
`max_query_events` a type): the span `ur.history_read`'s `events` summed over
the measured window, over the window's queries."""

from benchmarks.ur_metrics import history_events_per_query as read  # noqa: F401
