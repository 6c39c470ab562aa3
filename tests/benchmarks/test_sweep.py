"""`benchmarks/sweep.py` (CPU rehearsals, seconds each): one set-up, a window
a rate with a traffic seed of its own, the program's spans beside each, the
comparison last; and no window is opened past the deadline."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark_smoke import COMMITTED, root_with, run_script  # noqa: E402

SWEEP = os.path.join(ROOT, "benchmarks", "sweep.py")
SHARDED = "als-webgraph-desparse-d128.serve-sharded"


def sweep(tmp_path, *args):
    done = run_script(SWEEP, ["--workload", SHARDED, "--seed", "2147483999",
                              "--rehearsal", *args],
                      root_with(tmp_path, COMMITTED))
    assert done.returncode == 0, done.stderr[-2000:]
    return [json.loads(line) for line in done.stdout.splitlines()]


def test_sweep_runs_a_window_a_rate_on_one_setup(tmp_path):
    rows = sweep(tmp_path, "--rates", "20,40", "--seconds", "2")
    assert [r["step"] for r in rows] == [
        "setup", "rate", "rate", "memory", "check"]
    assert rows[0]["device"]["count"] == 4  # the driver's rehearsal_env
    assert "sharded.stage.transfer" in rows[0]["spans"]
    first, second = rows[1], rows[2]
    assert (first["offered"], second["offered"]) == (20.0, 40.0)
    assert second["traffic_seed"] == first["traffic_seed"] + 1
    for row in (first, second):
        assert row["failed"] == 0 and row["sustained"] is True
        spans = row["spans"]
        assert spans["sharded.dispatch"]["n"] == row["batches"]
        # the put is the dispatch's child: its time is not the parent's own
        assert spans["sharded.dispatch.put"]["n"] == row["batches"]
        assert (spans["sharded.dispatch"]["self_ms"]
                < spans["sharded.dispatch"]["ms"])
    assert all(c["ok"] for c in rows[-1]["checks"].values()), rows[-1]
    assert rows[-1]["checks"]["sharded_tier_inactive"]["value"] == 0.0


def test_sweep_opens_no_window_past_its_deadline(tmp_path):
    rows = sweep(tmp_path, "--rates", "20,40", "--seconds", "2",
                 "--deadline-s", "1")
    assert [r["step"] for r in rows] == ["setup", "stopped", "memory"]
    assert rows[1] == {"step": "stopped", "reason": "deadline",
                       "next_rate": 20.0}
