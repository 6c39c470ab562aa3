"""What the Universal Recommender cell's per-layer metrics share: the
operation and byte counts of its device work and the readers (a metric's own
file under layer_metrics/ names it; the arithmetic is here once).

A batch runs ONE device program (`models/cco.py` `_score_topk_jit`; a batch
whose plan outgrows a call runs the add-only `_accumulate_jit` first), in two
pieces. The SCORING ops: the tables are resident inverted, a thing's postings
contiguous, and a batch reads the postings of the things its queries'
histories name — an int32 item and a float32 weight each, once — and adds
each weight into a float32 total, which is written once for every item and
live query. What it must read is the traffic's: the DRIVER counts it, from
the configuration's tables and the histories it inserted (`postings_named`
in drivers/http_open_loop_history.py: for each scheduled query the slots
that name a distinct thing of the user's latest 100 of a type), never from
the program's plan, so a program that plans, pads or de-duplicates otherwise
moves the time and not the count. The TAIL, the fused exclusion + top-k
kernel (`ops/recommend_pallas.fused_masked_topk`): those totals read once.
Both are bound by the memory roof (an add a posting against 8 bytes).

`step_mfu_pct` alone counts from the configuration, whatever implements it:
a multiply and an add for every correlator slot and live query, what scoring
every item against a history takes without an index.
"""

from __future__ import annotations

from benchmarks import roofline

PROGRAM = "_score_topk_jit"  # the jitted programs' names in the trace
ADD_ONLY = "_accumulate_jit"
KERNEL = "fused_masked_topk"  # the Pallas custom call's name in the trace


def table_entries(cfg: dict) -> float:
    """Correlator slots of all indicators: items x correlators x indicators."""
    return (float(cfg["n_items"]) * len(cfg["indicators"])
            * cfg["algorithm"]["max_correlators_per_item"])


def query_flops(cfg: dict) -> float:
    """Operations ONE live query needs: a multiply and an add for every
    correlator slot (2 x 4 x 50 x 4,162,024 at the cell's shape)."""
    return 2.0 * table_entries(cfg)


def score_cost(cfg: dict, live_queries: float,
               postings: float) -> tuple[float, float]:
    """(operations, bytes) of one batch's scoring: its histories' postings
    read once (8 bytes each) and added, the totals written once (4 bytes an
    item and live query)."""
    return postings, 8.0 * postings + 4.0 * live_queries * cfg["n_items"]


def topk_cost(cfg: dict, live_queries: float) -> tuple[float, float]:
    """(operations, bytes) of one batch's exclusion + top-k: the totals
    read once; a compare an item and query."""
    entries = live_queries * float(cfg["n_items"])
    return entries, 4.0 * entries


def runs_of(trace, program: str) -> list[float]:
    """Device seconds of each run of a program in the measured window."""
    return [s for name, runs in trace.program_runs.items()
            if program in name for s in runs]


def kernel_seconds(trace) -> tuple[float, int]:
    """(device seconds, runs) of the tail kernel in the measured window."""
    names = [n for n in trace.op_seconds if n.lstrip("%").startswith(KERNEL)]
    return (sum(trace.op_seconds[n] for n in names),
            sum(trace.op_counts.get(n, 0) for n in names))


def live_queries_a_batch(reading):
    batches = reading.window.get("batches", 0)
    if not batches:
        return None
    return reading.window["batched_queries"] / batches


def batch_device_ms(reading):
    """Device time of one batch: every run of the scoring program
    `_score_topk_jit` AND of the add-only `_accumulate_jit` in the measured
    window (trace, "XLA Modules" line), over the batches — a batch runs the
    first once, so time moved from one program to the other stays in
    sight."""
    if reading.trace is None:
        return None
    runs = runs_of(reading.trace, PROGRAM)
    if not runs:
        return None
    return 1000.0 * (sum(runs) + sum(runs_of(reading.trace, ADD_ONLY))) / len(runs)


def score_roofline_pct(reading):
    """The scoring ops' share of their (memory) roofline: `score_cost` of
    the window's mean batch — its live queries, the postings its histories
    name by the driver's count — a batch, over the device time of the
    program's runs (and the add-only program's) less the tail kernel's."""
    trace, live = reading.trace, live_queries_a_batch(reading)
    postings = reading.window.get("postings_named")
    if trace is None or live is None or not postings:
        return None
    runs = runs_of(trace, PROGRAM)
    score_s = (sum(runs) + sum(runs_of(trace, ADD_ONLY))
               - kernel_seconds(trace)[0])
    if not runs or score_s <= 0:
        return None
    least, _bound = roofline.roofline_seconds(
        *score_cost(reading.config, live,
                    postings / reading.window["batches"]), reading.peaks)
    # a run a batch the trace holds (it may end before the window does:
    # `covered_share`), each the window's mean batch
    return 100.0 * least * len(runs) / score_s


def fused_topk_roofline_pct(reading):
    """The tail kernel's share of its (memory) roofline: `topk_cost` of the
    window's mean live batch, a run, over the kernel's device time."""
    trace, live = reading.trace, live_queries_a_batch(reading)
    if trace is None or live is None:
        return None
    seconds, runs = kernel_seconds(trace)
    if seconds <= 0 or not runs:
        return None
    least, _bound = roofline.roofline_seconds(
        *topk_cost(reading.config, live), reading.peaks)
    return 100.0 * least * runs / seconds


def covered_share(reading):
    """Share of the window's batches whose scoring program the device trace
    holds. The profiler keeps a bounded number of device events and this
    program leaves one a posting window (some 1,300 a batch): at 100
    queries/s the trace ends ~27 s into the 45 s window (PERF.md, PR 35).
    What the trace holds is the window's first part, so a quantity over the
    WHOLE window (busy seconds, the queries served) is scaled by this share;
    a ratio within the trace (time a batch, a roofline) needs no scaling."""
    trace = reading.trace
    batches = reading.window.get("batches", 0)
    if trace is None or not batches:
        return None
    runs = len(runs_of(trace, PROGRAM))
    return min(1.0, runs / batches) if runs else None


def idle_pct(reading):
    """Share of the measured window in which no operation ran on the device:
    1 - the union of the trace's "XLA Ops" intervals, over the part of the
    window the trace covers (`covered_share`)."""
    share = covered_share(reading)
    if share is None:
        return None
    trace = reading.trace
    return 100.0 * (1.0 - min(trace.busy_s / (share * trace.window_s), 1.0))


def step_mfu_pct(reading):
    """The whole serving step's share of the chip's bf16 peak:
    `query_flops` a live query, from the configuration alone, over the
    device time of every program that ran in the measured window (the
    queries of the part the trace covers: `covered_share`)."""
    trace, share = reading.trace, covered_share(reading)
    queries = reading.window.get("batched_queries", 0)
    if share is None or not queries:
        return None
    device_s = sum(sum(runs) for runs in trace.program_runs.values())
    if device_s <= 0:
        return None
    return (100.0 * share * queries * query_flops(reading.config)
            / (device_s * reading.peaks["bf16_flops"]))


def history_events_per_query(reading):
    """Events the batches' history reads returned (the span `ur.history_read`'s
    `events`, summed over the measured window by the driver's bridge) over
    the window's queries."""
    events = reading.window.get("history_events")
    queries = reading.window.get("batched_queries", 0)
    if not events or not queries:
        return None
    return events / queries
