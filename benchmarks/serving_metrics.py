"""Readers that more than one serving cell's per-layer metrics share (a
metric's own file under layer_metrics/ names which end-to-end metric it
moves and in which cells; the arithmetic is here once)."""

from __future__ import annotations

from benchmarks import roofline

ITEM_PAD = 128  # ops/recommend_pallas.py pads the item rows to this
KERNEL = "fused_recommend_topk"  # the Pallas custom call's name in the trace


def fused_recommend_roofline_pct(reading):
    """The fused score + top-k kernel's share of its roofline.

    What a pass must do at the least, from shapes: stream the padded item
    table once whatever the batch, and 2 * B_live * K * I_p operations
    (`roofline.fused_recommend_cost`; at these shapes the memory roof bounds
    it up to hundreds of queries a batch). B_live is the window's mean live
    batch from the dispatcher's counter. Over the device time of the
    kernel's runs in the measured window (trace, "XLA Ops" line)."""
    trace = reading.trace
    batches = reading.window.get("batches", 0)
    if trace is None or not batches:
        return None
    names = [n for n in trace.op_seconds if n.lstrip("%").startswith(KERNEL)]
    kernel_s = sum(trace.op_seconds[n] for n in names)
    runs = sum(trace.op_counts.get(n, 0) for n in names)
    if kernel_s <= 0 or not runs:
        return None
    cfg = reading.config
    flops, nbytes = roofline.fused_recommend_cost(
        roofline.pad_to(cfg["n_items"], ITEM_PAD), cfg["algorithm"]["rank"],
        batch_rows=64, live_queries=reading.window["batched_queries"] / batches,
        itemsize=4,
    )
    least, _bound = roofline.roofline_seconds(flops, nbytes, reading.peaks)
    return 100.0 * least * runs / kernel_s


def serve_step_mfu_pct(reading):
    """The whole serving step's share of the chip's bf16 peak: an exact top-k
    has to score every item for every live query, 2 * K * n_items operations
    a query (`roofline.serve_needed_flops`, from the configuration alone),
    over the device time of every program that ran in the measured window."""
    trace = reading.trace
    queries = reading.window.get("batched_queries", 0)
    if trace is None or not queries:
        return None
    device_s = sum(sum(runs) for runs in trace.program_runs.values())
    if device_s <= 0:
        return None
    cfg = reading.config
    need = roofline.serve_needed_flops(
        queries, cfg["algorithm"]["rank"], cfg["n_items"])
    return 100.0 * need / (device_s * reading.peaks["bf16_flops"])
