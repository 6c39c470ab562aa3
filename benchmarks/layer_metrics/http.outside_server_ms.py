"""What a query spends outside the server's own request span: the mean
latency of the window's answered queries at the generator (due on the
open-loop schedule -> last byte of the reply) minus the mean of the span
`server.request` — generator lateness, the socket both ways, the request
line and headers."""

from benchmarks.serve_split_metrics import outside_server_ms as read  # noqa: F401
