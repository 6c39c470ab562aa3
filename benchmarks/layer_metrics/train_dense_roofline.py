"""The dense train program's share of its roofline.

What the program's 2 x iterations half-steps must do at the least, from the
padded shape: each multiplies R by K + K^2 columns and reads R once
(`roofline.dense_half_step_cost`; the XLA form reads R twice and shows that
as a lower share). Over the device time of the program's runs in the traced
window (trace, "XLA Modules" line) — the conjugate-gradient solves and the
start are inside that time and counted as nothing, so the share reads low
rather than high."""

from benchmarks import roofline

PROGRAM = "_train_jit_dense"
ROW_BLOCK = 2048  # the dense path's padding quanta (ops/dense.py)
COL_PAD = 256


def read(reading):
    trace = reading.trace
    if trace is None or not trace.program_runs.get(PROGRAM):
        return None
    cfg, algo = reading.config, reading.config["algorithm"]
    flops, nbytes = roofline.dense_half_step_cost(
        roofline.pad_to(cfg["n_users"], ROW_BLOCK),
        roofline.pad_to(cfg["n_items"], COL_PAD),
        algo["rank"], cell_bytes=1,
    )
    least, _bound = roofline.roofline_seconds(flops, nbytes, reading.peaks)
    runs = trace.program_runs[PROGRAM]
    least_total = least * 2 * algo["num_iterations"] * len(runs)
    return 100.0 * least_total / sum(runs)
