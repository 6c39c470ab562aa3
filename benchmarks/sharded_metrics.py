"""Readers of the sharded serving tier's per-layer metrics (a metric's own
file under layer_metrics/ names it; the arithmetic is here once).

What the trace gives: `TraceSummary` keeps the operations and the program
runs of the FIRST device plane, and `busy_s` averaged over the planes. The
sharded program is symmetric — every shard gathers by the same `psum`,
streams a slab of the same padded size through the same kernel, and merges
by the same `all_gather` — and its collectives keep the shards in step, so
one plane's times are every plane's to within a collective's wait; the
readers use what the summary has.
"""

from __future__ import annotations

import dataclasses
import re
import statistics

from benchmarks import roofline, serving_metrics
from benchmarks.serving_metrics import ITEM_PAD

PROGRAM = "_sharded_recommend"  # the jitted program's name in the trace
_COLLECTIVE = re.compile(r"^%?all-(reduce|gather)[\w\-]*[.\s=(]|"
                         r"\sall-(reduce|gather)(-start|-done)?\(")


def program_runs(trace) -> list[float]:
    """Device seconds of each run of the sharded recommend program in the
    measured window, on the first device."""
    return [s for name, runs in trace.program_runs.items()
            if PROGRAM in name for s in runs]


def batch_device_ms(reading):
    """Device time of one sharded batch: the runs of `_sharded_recommend`
    (trace, "XLA Modules" line, first device), median."""
    trace = reading.trace
    if trace is None:
        return None
    runs = program_runs(trace)
    if not runs:
        return None
    return 1000.0 * statistics.median(runs)


def is_collective(op_name: str) -> bool:
    """An all-reduce or an all-gather (plain, or the start / done halves
    of an asynchronous one), by the operation's name in the trace."""
    return bool(_COLLECTIVE.search(op_name))


def collective_ms(reading):
    """Self time of the all-reduce and all-gather operations (the query
    block's gather by `psum`, the candidates' merge) on the first device,
    over the program's runs: milliseconds a batch. A collective's time
    holds its wait for the slowest shard."""
    trace = reading.trace
    if trace is None:
        return None
    runs = len(program_runs(trace))
    names = [n for n in trace.op_seconds if is_collective(n)]
    if not runs or not names:
        return None
    return 1000.0 * sum(trace.op_seconds[n] for n in names) / runs


def shard_item_rows(cfg: dict, shards: int) -> int:
    """Padded item rows of one shard: the catalogue pads to shards x
    ITEM_PAD rows (fleet/runtime.py) and splits evenly."""
    return roofline.pad_to(cfg["n_items"], shards * ITEM_PAD) // shards


def fused_recommend_roofline_pct(reading):
    """The shard-local fused score + top-k kernel's share of its roofline:
    `serving_metrics.fused_recommend_roofline_pct` with one shard's padded
    rows for the catalogue — what one shard's pass must do at the least is
    stream its slab once whatever the batch, and 2 * B_live * K * I_p / S
    operations (`roofline.fused_recommend_cost`; the memory roof bounds
    it), over the kernel's runs on the first device (trace, "XLA Ops")."""
    if reading.trace is None:
        return None
    shard = dict(reading.config, n_items=shard_item_rows(
        reading.config, reading.trace.n_devices))
    return serving_metrics.fused_recommend_roofline_pct(
        dataclasses.replace(reading, config=shard))


def step_mfu_pct(reading):
    """The whole sharded step's share of the chips' bf16 peak:
    `serving_metrics.serve_step_mfu_pct` (2 * K * n_items operations a live
    query, from the configuration alone, over the device time of every
    program on the first device x one chip's peak) over the number of
    chips, each of which runs the same programs for as long."""
    one_chip = serving_metrics.serve_step_mfu_pct(reading)
    if one_chip is None:
        return None
    return one_chip / reading.trace.n_devices


def exclusion_mb_per_batch(reading):
    """Megabytes of packed exclusion words the host built and shipped, a
    batch of the sharded tier, over the measured window: the server
    registry's `sharded_exclusion_bytes_total` over
    `sharded_batches_total` (every form counted), as the driver read them
    around the window."""
    batches = reading.window.get("sharded_batches")
    if not batches:
        return None
    return reading.window["sharded_exclusion_bytes"] / batches / 1e6
