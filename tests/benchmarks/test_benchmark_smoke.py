"""Tier-1 checks of the chip benchmark's harness (CPU, seconds each).

Nothing here touches a chip or describes a topology: the runs are
`--rehearsal` runs (tiny sizes, Pallas interpreter), each in a process of
its own, and the arithmetic (trace reduction, operation and byte counts,
traffic generation) is checked on recorded or hand-worked inputs.
"""

from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness, loadgen, roofline, trace_reduce  # noqa: E402

COMMITTED = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
STAGED = harness.load_json(
    os.path.join(ROOT, "benchmarks", "staged", "serving_cells.json"))
#: what BENCHMARK.json holds plus the cells that are ready but not admitted
#: (benchmarks/staged/): the tests keep both working
BENCHMARK = dict(COMMITTED, **{
    sec: COMMITTED[sec] + STAGED[sec]
    for sec in ("configs", "workloads", "end_to_end", "per_layer")})
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
HERE = os.path.dirname(os.path.abspath(__file__))
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_script(script, args, root, timeout=600):
    """A benchmark script in a process of its own, on one CPU device."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("PIO_")}
    env["JAX_PLATFORMS"] = "cpu"
    # one CPU device, like one chip, and few threads: the suite's other
    # workers run timing-sensitive tests beside these processes
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=1 "
                        "--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=2")
    for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
        env[var] = "2"
    return subprocess.run(
        [sys.executable, script] + args + ["--root", str(root)],
        capture_output=True, text=True, env=env, timeout=timeout, cwd=ROOT)


def run_cli(args, root):
    return run_script(os.path.join(ROOT, "benchmarks", "run.py"), args, root)


@pytest.fixture(scope="module")
def full_root(tmp_path_factory):
    return root_with(tmp_path_factory.mktemp("full"), BENCHMARK)


def workload_file(cell: str) -> dict:
    return harness.load_json(
        os.path.join(ROOT, "benchmarks", "workloads", cell + ".json"))


def root_with(tmp_path, benchmark: dict):
    """A --root that holds its own BENCHMARK.json, so that a run's state
    lands under tmp_path and not in the checkout."""
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(benchmark, f)
    return tmp_path


# -- the files fit together ---------------------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell, full_root):
    plan = harness.load_plan(str(full_root), cell)
    assert plan.config["name"] == plan.cell["config"]
    assert plan.workload["why"] and plan.workload["limits"]
    driver = harness.load_module(plan, "drivers", plan.workload["driver"])
    for hook in ("setup", "window", "check", "teardown", "prove"):
        assert callable(getattr(driver, hook))
    end_to_end = {m["name"] for m in plan.metrics("end_to_end")}
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    layer = plan.metrics("per_layer")
    assert layer, "every cell reports a per-layer metric"
    assert any(m["name"].startswith("device.idle_pct") for m in layer)
    for m in layer:
        assert m["moves"] in end_to_end
        reader = harness.load_module(plan, "layer_metrics", m["name"])
        assert callable(reader.read)


def test_every_metric_has_a_cell_and_a_layer_name():
    cells = set(CELLS)
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        for w in m.get("workloads", []):
            assert w in cells
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for m in BENCHMARK["per_layer"]:
        assert f"**{m['layer']}**" in perf, m["layer"]


# -- a run, end to end, tiny ---------------------------------------------------


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line_without_numbers(cell, trace, tmp_path):
    root = root_with(tmp_path, BENCHMARK)
    out = run_cli(["--workload", cell, "--seed", "2147483659", "--seconds", "2",
                   "--trace", str(trace), "--rehearsal"], root=root)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(line)
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    section = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in BENCHMARK[section]
             if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
    # a CPU run measures the CPU: the names, never a number under them
    assert all(v["value"] is None for v in line["metrics"].values())
    for name in names:
        assert f'"{name}": {{"value": null' in out.stdout or name not in out.stdout
    assert "breakdown" not in line and "notes" not in line
    # the numbers compared stand beside their limits, last on stderr
    last = out.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(ln.startswith("check ") and "limit=" in ln for ln in last)


def test_run_refuses_to_report_without_a_tpu(tmp_path):
    root = root_with(tmp_path, BENCHMARK)
    out = run_cli(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"], root=root)
    assert out.returncode == 3
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr


def test_a_new_cell_and_metric_need_only_new_files(tmp_path):
    """A later PR adds files and entries; run.py is not edited."""
    base = CELLS[0]
    new = base.rsplit(".", 1)[0] + ".throwaway"
    bench = json.loads(json.dumps(BENCHMARK))
    cell = dict(next(w for w in bench["workloads"] if w["name"] == base),
                name=new, traffic="throwaway")
    bench["workloads"].append(cell)
    for m in bench["end_to_end"]:
        if "workloads" in m and base in m["workloads"]:
            m["workloads"].append(new)
    moved = next(m["name"] for m in bench["end_to_end"]
                 if base in m.get("workloads", []))
    bench["per_layer"].append({
        "name": "throwaway.answer", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Device", "moves": moved,
        "workloads": [new]})
    root = root_with(tmp_path, bench)
    os.makedirs(tmp_path / "benchmarks" / "workloads")
    os.makedirs(tmp_path / "benchmarks" / "layer_metrics")
    wl = harness.load_json(
        os.path.join(ROOT, "benchmarks", "workloads", base + ".json"))
    wl["name"] = new
    with open(tmp_path / "benchmarks" / "workloads" / (new + ".json"), "w") as f:
        json.dump(wl, f)
    with open(tmp_path / "benchmarks" / "layer_metrics" / "throwaway.answer.py", "w") as f:
        f.write("def read(reading):\n    return 42.0\n")
    out = run_cli(["--workload", new, "--seed", "3", "--seconds", "1",
                   "--trace", "1", "--rehearsal"], root=root)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["metrics"] == {"throwaway.answer": {"value": None, "unit": "count"}}


# -- `correct` can come out false ---------------------------------------------


FAULTS = {
    "train_jobs": ["state_unchanged", "half_left_out", "row_altered"],
    "http_closed_loop": ["item_altered", "score_altered", "reply_dropped"],
    "http_open_loop": ["item_altered"],
}
FAULT_CASES = sorted({(workload_file(c)["driver"], c) for c in CELLS})


@pytest.mark.parametrize(
    "cell,fault",
    [(c, f) for d, c in FAULT_CASES for f in FAULTS.get(d, [])])
def test_a_broken_timed_path_reads_not_correct(cell, fault, tmp_path):
    """The whole run, but for the look for a chip, with the program's timed
    path broken underneath (tests/benchmarks/fault_runner.py plants it)."""
    root = root_with(tmp_path, BENCHMARK)
    out = run_script(
        os.path.join(HERE, "fault_runner.py"),
        [fault, "--workload", cell, "--seed", "77", "--seconds", "1",
         "--trace", "0", "--rehearsal"], root)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line["checks"]
    assert any(not c["ok"] for c in line["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_fails_the_limits(cell, tmp_path):
    """The control (a run in the nearest precision below the one the
    configuration states) reads over a limit at the tiny size too; the
    program reads under every one."""
    root = root_with(tmp_path, BENCHMARK)
    out = run_script(
        os.path.join(ROOT, "benchmarks", "prove.py"),
        ["--workload", cell, "--seeds", "41", "--controls", "1",
         "--seconds", "1", "--rehearsal"], root)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    limits = workload_file(cell)["limits"]

    def over(numbers):
        return [k for k, lim in limits.items() if not numbers[k] <= lim]

    assert over(got["program"]) == []
    controls = [k for k in got if k.startswith("control_")]
    assert controls
    for k in controls:
        assert over(got[k]), (k, got[k])
    for k in (k for k in got if k.startswith("fault_")):
        zero_limits = [n for n, v in got[k].items()
                       if n not in limits and v > 0]
        assert over(got[k]) or zero_limits, (k, got[k])


# -- the yardstick's arithmetic ------------------------------------------------


def recorded_trace():
    with gzip.open(os.path.join(HERE, "recorded_trace.json.gz"), "rt") as f:
        return json.load(f)


def test_trace_reducer_on_a_handmade_trace():
    dev = trace_reduce.DEVICE_PLANE_PREFIX + "0"
    trace = {"planes": [
        {"name": dev, "lines": [
            {"name": trace_reduce.OPS_LINE, "events": [
                ["while.1", 1_000_000, 4_000_000],  # encloses the next two
                ["fusion.2", 1_000_000, 1_500_000],
                ["fusion.3", 3_000_000, 1_000_000],
                ["copy.4", 8_000_000, 2_000_000],
            ]},
            {"name": trace_reduce.MODULES_LINE, "events": [
                ["jit_step(123)", 1_000_000, 4_000_000],
                ["jit_step(123)", 8_000_000, 2_000_000],
            ]},
        ]},
        {"name": trace_reduce.HOST_PLANE, "lines": [
            {"name": "python3", "events": [
                ["outer", 0, 20_000_000], ["np.unique", 5_500_000, 2_000_000],
            ]},
        ]},
    ]}
    s = trace_reduce.reduce_trace(trace, window_s=0.010)
    assert s.busy_s == pytest.approx(0.006)
    assert s.idle_share == pytest.approx(0.4)
    assert s.op_seconds == pytest.approx(
        {"while.1": 0.0015, "fusion.2": 0.0015, "fusion.3": 0.001,
         "copy.4": 0.002})
    assert s.program_runs == {"step": [pytest.approx(0.004), pytest.approx(0.002)]}
    # idle: 1 ms before the first operation (the window's edge counts), the
    # 3 ms gap sampled at 16 instants of which np.unique covers 10
    assert dict(s.idle_gaps) == pytest.approx(
        {"outer": 0.001 + 0.003 * 6 / 16, "np.unique": 0.003 * 10 / 16})
    clipped = trace_reduce.reduce_trace(trace, clip=(0.005, 0.010))
    assert clipped.window_s == pytest.approx(0.005)
    assert clipped.busy_s == pytest.approx(0.002)
    assert clipped.program_runs == {"step": [pytest.approx(0.002)]}
    assert s.breakdown()["device_ops"][0] == ["copy.4", pytest.approx(0.002)]


def test_trace_reducer_on_the_recorded_chip_trace():
    """A cut of a real v5e trace of the train cell (PR 24): the reducer finds
    the device plane, both lines and the programs by name."""
    expect = harness.load_json(os.path.join(HERE, "recorded_trace.expect.json"))
    s = trace_reduce.reduce_trace(recorded_trace(), window_s=expect["window_s"])
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(expect["busy_s"], rel=1e-9)
    for name, seconds in expect["program_seconds"].items():
        assert s.program_seconds(name) == pytest.approx(seconds, rel=1e-9)
    top = s.breakdown()["device_ops"][0]
    assert top[0] == expect["top_op"]
    assert 0.0 < s.idle_share < 1.0


def test_no_device_plane_is_an_error_not_a_zero():
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"planes": [
            {"name": trace_reduce.HOST_PLANE, "lines": []}]})


@pytest.mark.parametrize("fn,args,expect", [
    # implicit ALS's need: 2 users x 3 items, 4 pairs, rank 2, 1 iteration,
    # 1 CG step: edge 2*(2*4*(2+4))=96, gram 2*5*4=40, cg 5*2*2*4=80
    (roofline.als_needed_flops, (2, 3, 4, 2, 1, 1), 96 + 40 + 80),
    # the need ignores padding: the same at any padded shape, linear in nnz
    (roofline.als_needed_flops, (2, 3, 8, 2, 1, 1), 192 + 40 + 80),
    (roofline.serve_needed_flops, (3, 4, 10), 2 * 3 * 4 * 10),
])
def test_needed_operations_on_hand_worked_shapes(fn, args, expect):
    assert fn(*args) == pytest.approx(expect)


def test_the_dense_kernels_count_holds_the_padding_and_the_needs_does_not():
    # 2048 x 256 cells at one byte, rank 2: 2*cells*(2+4) operations
    flops, nbytes = roofline.dense_half_step_cost(2048, 256, 2, 1)
    assert flops == 2 * 2048 * 256 * 6
    assert nbytes == 2048 * 256 + 4 * 2048 * 6 * 2
    bigger, _ = roofline.dense_half_step_cost(4096, 256, 2, 1)
    assert bigger == 2 * flops  # padding rows are paid for
    need = roofline.als_needed_flops(2048, 256, 1000, 2, 1, 1)
    assert need == roofline.als_needed_flops(2048, 256, 1000, 2, 1, 1)
    assert need < flops  # R is sparse: the need is far under the spend
    assert roofline.pad_to(463_435, 2048) == 464_896
    assert roofline.pad_to(17_769, 256) == 17_920


def test_fused_recommend_cost_streams_the_table_once_whatever_the_batch():
    f8, b8 = roofline.fused_recommend_cost(5_700_096, 128, 8, 8, 4)
    f64, b64 = roofline.fused_recommend_cost(5_700_096, 128, 64, 64, 4)
    assert f64 == 8 * f8
    assert b8 == pytest.approx(5_700_096 * 128 * 4, rel=1e-5)
    assert b64 == pytest.approx(b8, rel=1e-4)
    peak = roofline.peaks_for("TPU v5 lite")
    seconds, bound = roofline.roofline_seconds(f64, b64, peak)
    assert bound == "memory" and seconds == pytest.approx(b64 / 819e9)


def test_an_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9 imaginary")


# -- traffic depends on the seed and the file alone ----------------------------


TRAFFIC = {"loop": "open", "rate_qps": 200.0, "user_zipf_s": 1.0, "num": 10,
           "blacklist_share": 0.15, "blacklist_max": 8}


def test_the_first_arrivals_and_queries_of_one_seed_are_pinned():
    t = loadgen.arrival_times(200.0, 5.0, seed=12345, stream=0)
    assert len(t) == 1000 and np.all(np.diff(t) > 0) and t[-1] < 5.0
    assert np.round(t[:4], 6).tolist() == [0.004669, 0.006164, 0.014481, 0.042756]
    # another seed: the same gaps in another order, so the same count
    other_t = loadgen.arrival_times(200.0, 5.0, seed=12346, stream=0)
    assert len(other_t) == 1000 and not np.allclose(other_t, t)
    gaps = np.round(-np.log1p(-(np.arange(1000) + 0.5) / 1000) / 200.0, 9)
    for arrivals in (t, other_t):
        assert np.isin(np.round(np.diff(arrivals), 9), gaps).all()
    first = loadgen.fixed_mix(1000, TRAFFIC, 5_700_000, 5_700_000, 12345, 2000)
    assert first[:2] == [{"user": "u3287445", "num": 10},
                         {"user": "u2080110", "num": 10}]
    assert first[2]["blacklist"] == [
        "i3712908", "i4280068", "i1424080", "i3359154", "i623672", "i1041647"]
    assert loadgen.fixed_mix(
        1000, TRAFFIC, 5_700_000, 5_700_000, 12345, 2000) == first
    other = loadgen.fixed_mix(1000, TRAFFIC, 5_700_000, 5_700_000, 12346, 2000)
    assert other != first

    def sizes(qs):
        return sorted(len(q.get("blacklist", [])) for q in qs)

    # every seed: the same number of blacklists of the same sizes
    assert sizes(other) == sizes(first) and sum(map(bool, sizes(first))) == 150
    assert max(sizes(first)) == 8
    # the closed loop's stream: a function of (seed, stream) too
    qs = loadgen.QueryStream(TRAFFIC, 5_700_000, 5_700_000, 12345, 2000)
    again = loadgen.QueryStream(TRAFFIC, 5_700_000, 5_700_000, 12345, 2000)
    stream = [qs.next() for _ in range(400)]
    assert [again.next() for _ in range(400)] == stream
    assert [q["user"] for q in stream[:4]] == [
        "u4244955", "u963342", "u4345006", "u4421721"]
    share = sum("blacklist" in q for q in stream) / len(stream)
    assert 0.08 < share < 0.24


def test_zipf_draw_is_heavy_at_the_head_and_covers_the_tail():
    cdf = loadgen.zipf_cdf(5_700_000, 1.0)
    assert cdf[0] == pytest.approx(1 / 16.13, rel=0.01)  # 1 / H_n
    assert cdf[-1] == 1.0
    stride = loadgen.user_stride(5_700_000)
    rows = (np.arange(1, 1001, dtype=np.int64) * stride) % 5_700_000
    assert len(set(rows.tolist())) == 1000  # a bijection on the ranks


def test_the_corpus_depends_on_the_seed_alone_and_covers_every_row():
    from benchmarks import corpus

    a = corpus.make_corpus(300, 70, 4000, seed=2**31 + 11)
    b = corpus.make_corpus(300, 70, 4000, seed=2**31 + 11)
    c = corpus.make_corpus(300, 70, 4000, seed=5)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    rows, cols, vals = a
    assert len(rows) == 4000 and set(rows) == set(range(300))
    assert set(cols) == set(range(70)) and np.all(vals == 1.0)
    assert len(set(zip(rows.tolist(), cols.tolist()))) == 4000


# -- BENCHMARK.json keeps to the contract's form ---------------------------------


NAME = r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}"


@pytest.mark.parametrize("which", ["committed", "with_staged"])
def test_benchmark_json_keeps_to_the_contracts_form(which):
    import re

    b = COMMITTED if which == "committed" else BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in b["paths"])

    def line(text):
        return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text

    def under_paths(rel):
        return any(rel.startswith(p + "/") for p in b["paths"])

    assert all(not w.startswith("/") and ".." not in w for w in b["command"])
    assert under_paths(b["command"][1])
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.fullmatch(NAME, c["name"]) and line(c["source"]) and line(c["why"])
        assert under_paths(c["file"]) and c["file"] not in files
        files.add(c["file"])
        on_disk = harness.load_json(os.path.join(ROOT, c["file"]))
        assert on_disk["reduced"] == c["reduced"] and on_disk["source"] == c["source"]
    cells, pairs = set(), set()
    four = 0
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert re.fullmatch(NAME, w["name"]) and re.fullmatch(NAME, w["traffic"])
        assert line(w["why"]) and w["chips"] in (1, 4)
        assert w["config"] in {c["name"] for c in b["configs"]}
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cells.add(w["name"])
        four += w["chips"] == 4
    assert four <= max(1, len(cells) // 4)
    assert {c["name"] for c in b["configs"]} == {w["config"] for w in b["workloads"]}
    names = set()
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line(m["layer"])
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
    for m in b["end_to_end"] + b["per_layer"]:
        assert re.fullmatch(NAME, m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    # a whole-step share of the peak stands beside each kernel's roofline
    for m in b["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in re.split(r"[._]", o["name"])
                       and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in b["per_layer"])
    for root, _dirs, fnames in os.walk(os.path.join(ROOT, "benchmarks")):
        if "__pycache__" in root:
            continue
        for f in fnames:
            assert re.fullmatch(r"[A-Za-z0-9_.\-]+", f), f
