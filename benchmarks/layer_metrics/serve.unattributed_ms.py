"""What a request carries no span for, the serving twin of
`train.unattributed_s`: the mean `server.request` minus its self time and
the means of `query.decode`, `query.encode`, `batch.queue_wait`,
`batch.device_dispatch`, `batch.result_transfer` and `query.wake`, each
over the measured window's queries."""

from benchmarks.serve_split_metrics import request_unattributed_ms as read  # noqa: F401
