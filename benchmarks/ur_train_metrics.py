"""What the Universal Recommender train cell's device metrics share: the
least the join programs have to move, and the operations a train needs, from
the work the BENCHMARK counts (`drivers/ur_train_jobs.py` `pair_counts`: the
pairs and the distinct pairs of its own downsampled events, never the
program's counters), over device time from the trace.
"""

from __future__ import annotations

JOIN = "_pair_counts_jit"  # the join program's name in the trace
#: operations an LLR takes beyond its count: four cells of a divide, a
#: log and a multiply-add
LLR_OPS = 16


def join_least_bytes(work: dict) -> float:
    """Bytes one job's join programs (`_pair_counts_jit`) must move at the
    least: each pair read once as it comes in and written once sorted (an
    item and a thing, 8 B each way), and a count written once for each
    distinct pair (4 B). The expansion of the kept events into pairs runs
    on the host since PR 39, outside the timed program: the events are not
    counted."""
    return float(sum(16.0 * w["pairs"] + 4.0 * w["distinct"]
                     for w in work.values()))


def train_ops(work: dict) -> float:
    """Operations one job needs: an add for every pair and an LLR for
    every distinct pair."""
    return float(sum(w["pairs"] + LLR_OPS * w["distinct"]
                     for w in work.values()))


def _jobs_and_work(reading):
    jobs = len(reading.window.get("jobs", []))
    work = reading.window.get("pairs")
    if reading.trace is None or not jobs or not work:
        return None, None
    return jobs, work


def join_roofline_pct(reading):
    jobs, work = _jobs_and_work(reading)
    if jobs is None:
        return None
    runs = [s for name, rs in reading.trace.program_runs.items()
            if JOIN in name for s in rs]
    if not runs or sum(runs) <= 0:
        return None
    least = jobs * join_least_bytes(work) / reading.peaks["hbm_bytes_per_s"]
    return 100.0 * least / sum(runs)


def step_mfu_pct(reading):
    jobs, work = _jobs_and_work(reading)
    if jobs is None:
        return None
    device_s = sum(sum(r) for r in reading.trace.program_runs.values())
    if device_s <= 0:
        return None
    return (100.0 * jobs * train_ops(work)
            / (device_s * reading.peaks["bf16_flops"]))
