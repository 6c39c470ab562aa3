"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It knows no cell, configuration, driver or metric by name: BENCHMARK.json
names the cell's configuration file and its metrics, the cell's own file
(`benchmarks/workloads/<cell>.json`) names the driver
(`benchmarks/drivers/<driver>.py`) and carries the traffic's parameters, and
each per-layer metric is read by `benchmarks/layer_metrics/<metric>.py`. A
later PR adds files and entries; it edits nothing here.

A run: set-up (corpus or tables from --seed, the system under test, every
shape warmed up) -> the measured window -> peak memory is read -> the
program's state is freed -> the comparison with the plain reference decides
`correct` -> one JSON line, last on standard output. With `--trace 1` the
window runs under the jax profiler and the line carries the cell's per-layer
metrics in place of the end-to-end ones.

`--rehearsal` is for the CPU tests only: it takes the configuration's tiny
`rehearsal` sizes and lets the run go on without a TPU; such a run prints
every metric's name with `null`, never a number, because a time taken off a
CPU is not a measurement of this system. Without it a run that finds no TPU,
or fewer chips than the cell asks for, exits 3 and prints no result.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # process start, as near as Python lets us see it

import argparse
import gc
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmarks.harness import (  # noqa: E402  (after the path is set)
    BenchmarkError,
    Check,
    Reading,
    device_info,
    load_module,
    log,
    make_context,
    memory_peak_bytes,
    open_cell,
    start_trace,
)

EXIT_NO_DEVICE = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU tests only: tiny sizes, no numbers printed")
    ap.add_argument("--root", default=CHECKOUT,
                    help="directory that holds BENCHMARK.json (tests)")
    ap.add_argument("--keep-trace", default=None, metavar="FILE.json.gz",
                    help="also write the traced window's events, in the "
                         "plain form trace_reduce works on (for a look by "
                         "hand, and for the tests' recorded trace)")
    args = ap.parse_args(argv)

    plan, driver = open_cell(args.root, args.workload, args.rehearsal)

    import jax

    # the program's own entry points do the same (utils/jaxenv.py); small
    # programs are cached too, so that a second run compiles nothing
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    device = device_info(jax)
    chips = int(plan.cell["chips"])
    if not args.rehearsal and (
        device["platform"] != "tpu" or device["count"] < chips
    ):
        log(f"no accelerator for this cell: jax reports {device}, the cell "
            f"asks for {chips} TPU chip(s); nothing is reported")
        return EXIT_NO_DEVICE

    from benchmarks import roofline, trace_reduce

    peaks = None if args.rehearsal else roofline.peaks_for(device["kind"])
    ctx = make_context(plan, args.seed, args.seconds, bool(args.trace),
                       args.rehearsal)

    session = driver.setup(ctx)
    try:
        trace_dir = os.path.join(ctx.state_dir, "trace")
        setup_s = time.monotonic() - _T0
        log(f"set-up done in {setup_s:.2f}s; window of {args.seconds}s starts")
        if ctx.trace:
            start_trace(jax, trace_dir, plan.workload.get("trace", {}))
        t_window = time.monotonic()  # the trace's clock starts here too
        window = driver.window(session, ctx)
        traced_s = time.monotonic() - t_window
        if ctx.trace:
            jax.profiler.stop_trace()
        log(f"window closed after {traced_s:.2f}s")
        device["memory_peak_bytes"] = memory_peak_bytes(jax)
        gc.collect()
        checks: list[Check] = driver.check(session, ctx, window)
    finally:
        driver.teardown(session)

    summary = None
    if ctx.trace and not args.rehearsal:
        t0 = time.monotonic()
        events = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        # a driver whose measured window is a part of what was traced (the
        # generators start, the last replies come) says which part
        clip = window.get("measured_monotonic")
        if clip is not None:
            clip = (clip[0] - t_window, clip[1] - t_window)
        summary = trace_reduce.reduce_trace(events, window_s=traced_s, clip=clip)
        if args.keep_trace:
            trace_reduce.dump(events, args.keep_trace)
        log(f"trace read in {time.monotonic() - t0:.2f}s")
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    shutil.rmtree(trace_dir, ignore_errors=True)

    metrics: dict[str, dict] = {}
    if ctx.trace:
        reading = Reading(ctx.config, plan.workload, device["kind"], peaks,
                          window, summary)
        for m in plan.metrics("per_layer"):
            reader = load_module(plan, "layer_metrics", m["name"])
            # a reader that finds nothing to read (no trace, no span)
            # returns None and the metric is left out of the line
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(window.get("end_to_end", {}), setup_s=setup_s)
        for m in plan.metrics("end_to_end"):
            if m["name"] not in values:
                raise BenchmarkError(
                    f"driver {plan.workload['driver']} gave no {m['name']}"
                )
            metrics[m["name"]] = {
                "value": float(values[m["name"]]), "unit": m["unit"]
            }
    if args.rehearsal:
        # a CPU run measures the CPU: names, never numbers
        metrics = {k: {"value": None, "unit": v["unit"]}
                   for k, v in metrics.items()}

    correct = bool(checks) and all(c.ok for c in checks)
    result = {
        "correct": correct,
        "attempted": int(window["attempted"]),
        "failed": int(window["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    if args.rehearsal:
        result["rehearsal"] = True
    else:
        result["notes"] = window.get("notes", {})
    result["checks"] = {
        c.name: {"value": c.value, "limit": c.limit, "ok": c.ok}
        for c in checks
    }
    sys.stdout.flush()
    for c in checks:
        print(f"check {c.name}: value={c.value!r} limit={c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
