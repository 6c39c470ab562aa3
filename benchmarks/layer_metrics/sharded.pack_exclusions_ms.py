"""What the host spends building a sharded batch's packed exclusion words
(`(B, I_p/32)` words from a blacklist's row list or a dense filter): the span
`sharded.pack_exclusions`, total over count, over the measured window; a
batch with no exclusion records none, so this is per batch that has one."""

from benchmarks.span_metrics import mean_ms


def read(reading):
    return mean_ms(reading, "sharded.pack_exclusions")
