"""The rate a serving cell sustains, and how its median spreads: ONE set-up,
then an open-loop window at each of `--rates` in turn.

    python3 benchmarks/sweep.py --workload <cell> --seed 7 --rates 25,50,100,200,400,500,550 --seconds 12
    python3 benchmarks/sweep.py --workload <cell> --seed 7 --rates 400,300,400,300 --seconds 45 --deadline-s 480

Every window runs the cell's own driver and traffic with `rate_qps`
replaced and a traffic seed of its own (`--seed` + its place in the list;
the tables stay `--seed`'s); a rate is SUSTAINED when every query was
answered, at least 98 % of the offered rate completed inside the window
and the last reply came less than 0.5 s after it. One JSON object per
window on standard output, with the program's spans over the window
(count, milliseconds a span, self milliseconds) — so a workload file's
`rate_from` can be made again — then the comparison with the plain
reference on the last window's sample. `--deadline-s` opens no window that
would end later than that many seconds after the process started: a call
to the chips has a budget. The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse
import dataclasses
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmarks.harness import (  # noqa: E402
    device_info,
    log,
    make_context,
    memory_peak_bytes,
    open_cell,
)


def sustained(win: dict, rate: float) -> bool:
    notes = win["notes"]
    return bool(win["failed"] == 0
                and notes["achieved_qps"] >= 0.98 * rate
                and notes["last_reply_after_window_s"] < 0.5)


def span_table(win: dict) -> dict:
    """{span: {"n", "ms", "self_ms"}} over the measured window."""
    from predictionio_tpu.obs.spans import get_default_recorder

    stats = get_default_recorder().stats(*win["measured_monotonic"])
    return {name: {"n": row["count"],
                   "ms": 1000.0 * row["total_s"] / row["count"],
                   "self_ms": 1000.0 * row["self_s"] / row["count"]}
            for name, row in sorted(stats.items()) if row["count"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="offered rates, queries/s, in the order to run")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--root", default=CHECKOUT)
    args = ap.parse_args(argv)

    plan, driver = open_cell(args.root, args.workload, args.rehearsal)
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = device_info(jax)
    if not args.rehearsal and (device["platform"] != "tpu"
                               or device["count"] < int(plan.cell["chips"])):
        log(f"no accelerator for this cell: {device}")
        return 3

    def emit(**row):
        print(json.dumps(row), flush=True)

    ctx = make_context(plan, args.seed, args.seconds, False, args.rehearsal)
    # what a window costs beyond its seconds: the generators' start, the
    # last replies, the files read back
    spare = float(ctx.traffic.get("generator_start_s", 4.0)) + 5.0
    session = driver.setup(ctx)
    try:
        emit(step="setup", seconds=time.monotonic() - _T0, device=device,
             memory_peak_bytes=memory_peak_bytes(jax),
             spans=span_table({"measured_monotonic": (_T0, time.monotonic())}))
        win = None
        for place, rate in enumerate(float(r) for r in args.rates.split(",")):
            ends = time.monotonic() - _T0 + args.seconds + spare
            if args.deadline_s is not None and ends > args.deadline_s:
                emit(step="stopped", reason="deadline", next_rate=rate)
                break
            at = dataclasses.replace(
                ctx, seed=args.seed + place,
                traffic=dict(ctx.traffic, rate_qps=rate))
            win = driver.window(session, at)
            notes = win["notes"]
            emit(step="rate", offered=rate, traffic_seed=at.seed,
                 sustained=sustained(win, rate),
                 achieved_qps=notes["achieved_qps"],
                 attempted=win["attempted"], failed=win["failed"],
                 query_p50_ms=notes["query_p50_ms"],
                 query_p99_ms=notes["query_p99_ms"],
                 last_reply_after_window_s=notes["last_reply_after_window_s"],
                 generator_lateness_ms=notes["generator_lateness_ms"],
                 batches=win["batches"],
                 queries_a_batch=win["batched_queries"] / max(win["batches"], 1.0),
                 compiles_in_window=win["compiles_in_window"],
                 sharded_batches_by_form=notes.get("sharded_batches_by_form"),
                 sharded_exclusion_bytes=win.get("sharded_exclusion_bytes"),
                 spans=span_table(win))
        emit(step="memory", memory_peak_bytes=memory_peak_bytes(jax))
        if win is not None:
            emit(step="check", checks={
                c.name: {"value": c.value, "limit": c.limit, "ok": c.ok}
                for c in driver.check(session, at, win)})
    finally:
        driver.teardown(session)
    return 0


if __name__ == "__main__":
    sys.exit(main())
