"""The whole train's share of the chip's bf16 peak: the operations implicit
ALS NEEDS for a train, from the configuration alone
(`roofline.als_needed_flops`: observed pairs only, no padding, no zeros of
R), over the device time of every program the traced window's jobs ran
(densify, the train program, whatever a later PR puts in their place). It
reads the same work whatever implements it."""

from benchmarks import roofline


def read(reading):
    trace = reading.trace
    jobs = len(reading.window.get("jobs", []))
    if trace is None or not jobs:
        return None
    device_s = sum(sum(runs) for runs in trace.program_runs.values())
    if device_s <= 0:
        return None
    cfg, algo = reading.config, reading.config["algorithm"]
    need = roofline.als_needed_flops(
        cfg["n_users"], cfg["n_items"], cfg["n_interactions"], algo["rank"],
        algo["num_iterations"], algo["cg_iterations"],
    )
    return 100.0 * need * jobs / (device_s * reading.peaks["bf16_flops"])
