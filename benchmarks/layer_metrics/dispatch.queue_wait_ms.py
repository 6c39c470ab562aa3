"""How long a query waits in the dispatcher before its batch goes to the
device: the program's `batch.queue_wait` span as the server registry's
`batch_queue_wait_seconds` histogram keeps it, mean over the measured
window's queries."""


def read(reading):
    if not reading.window.get("batches"):
        return None
    return 1000.0 * reading.window["queue_wait_mean_s"]
