"""The Universal Recommender's train from sparse pairs (ISSUE 39): the LLR
that holds float32 at a million users, Mahout's downsampling, and the whole
`run_train` path against the plain float64 reference
(`benchmarks/reference/ur_cco.py`), small and seeded, on the CPU."""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import ur_train_data  # noqa: E402
from benchmarks.reference import ur_cco as ref  # noqa: E402
from predictionio_tpu.data.storage.registry import (  # noqa: E402
    SourceConfig,
    Storage,
    StorageConfig,
)
from predictionio_tpu.data.store.bimap import BiMap  # noqa: E402
from predictionio_tpu.models import cco  # noqa: E402

TAOBAO_N = 987_994


def dunning_f64(k11, r, c, n):
    """Dunning's LLR in float64, entropy form: the textbook formula."""
    k11, r, c = (np.asarray(x, np.float64) for x in (k11, r, c))
    k12, k21 = r - k11, c - k11
    k22 = n - r - c + k11

    def xlx(x):
        return np.where(x > 0, x * np.log(np.where(x > 0, x, 1.0)), 0.0)

    return 2.0 * (xlx(k11) + xlx(k12) + xlx(k21) + xlx(k22) - xlx(r)
                  - xlx(n - r) - xlx(c) - xlx(n - c) + xlx(np.float64(n)))


def entropy_f32(k11, r, c, n):
    """The form `llr_scores` computed before PR 39, in float32."""
    f = np.float32
    k11, r, c, n = (np.asarray(x, f) for x in (k11, r, c, n))
    k12, k21 = r - k11, c - k11
    k22 = n - k11 - k12 - k21

    def xlx(x):
        return np.where(x > 0, x * np.log(np.maximum(x, f(1e-30))), f(0))

    return np.maximum(f(2) * (
        xlx(k11) + xlx(k12) + xlx(k21) + xlx(k22) - xlx(k11 + k12)
        - xlx(k21 + k22) - xlx(k11 + k21) - xlx(k12 + k22) + xlx(n)), f(0))


def taobao_pairs(n=20_000, seed=0):
    """Typical pairs at the deployment's N: k11 1-3, an item's total up to
    50, a thing's up to 20,000."""
    rng = np.random.default_rng(seed)
    r = rng.integers(1, 51, n)
    c = rng.integers(1, 20_001, n)
    k11 = np.minimum(rng.integers(1, 4, n), np.minimum(r, c))
    return k11, r, c


@pytest.mark.parametrize("form,holds", [("llr_scores", True),
                                        ("entropy_f32", False)])
def test_llr_holds_1e4_relative_in_float32_at_taobaos_n(form, holds):
    k11, r, c = taobao_pairs()
    exact = dunning_f64(k11, r, c, TAOBAO_N)
    if form == "llr_scores":  # the dense entry point, on a diagonal
        got = np.diag(np.asarray(cco.llr_scores(
            np.diag(k11[:2000].astype(np.float32)),
            r[:2000].astype(np.float32), c[:2000].astype(np.float32),
            TAOBAO_N)))
        got = np.concatenate([got, np.asarray(cco.llr(
            k11[2000:], r[2000:], c[2000:], TAOBAO_N))])
    else:
        got = entropy_f32(k11, r, c, TAOBAO_N)
    gap = np.abs(got - exact) / np.maximum(1.0, exact)
    assert (gap.max() <= 1e-4) == holds, gap.max()


def test_llr_reads_zero_exactly_on_an_independent_table_and_positive_else():
    # d = k11 n - r c: 0 for (2, 40, 50, 1000); +-1 beside it
    k11 = np.array([2, 1, 3, 5, 0])
    r = np.array([40, 7, 11, 5, 9])
    c = np.array([50, 143, 91, 7, 9])
    n = 1000
    got = np.asarray(cco.llr(k11, r, c, n))
    assert got[0] == 0.0  # independent
    assert got[1] > 0 and got[2] > 0  # d = -1 and +-1: tiny, but there
    assert got[3] > 0  # k12 = 0: its cell adds 0, no NaN
    assert got[4] == 0.0  # never co-occurred
    assert np.all(np.isfinite(got))
    want = ref.llr(k11, r, c, n)
    np.testing.assert_allclose(got[3], want[3], rtol=1e-5)


def test_the_draw_and_the_kept_events_are_the_references():
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 300, 20_000)
    cols = rng.integers(0, 90, 20_000)
    assert np.array_equal(
        cco.sample_draw(cco.DOWNSAMPLE_SEED, 2, rows, cols),
        ref.draw(cco.DOWNSAMPLE_SEED, 2, rows, cols))
    grouped = cco.group_by_user(rows, cols, 300, 90)
    r_d, c_d = ref.distinct(rows, cols, 90)
    assert np.array_equal(grouped.cols, c_d)
    assert np.array_equal(grouped.rows(), r_d)
    kept = cco.downsample(grouped, 5, cco.DOWNSAMPLE_SEED, 2)
    r_k, c_k = ref.downsample(rows, cols, 300, 90, 5, cco.DOWNSAMPLE_SEED, 2)
    assert np.array_equal(kept.cols, c_k) and np.array_equal(kept.rows(), r_k)
    assert 0 < c_k.size < c_d.size  # the cap binds
    # a cap over every count keeps every distinct event
    assert cco.downsample(grouped, 10**6, 1, 2) is grouped


# -- the whole train path against the reference -------------------------------


SMALL = {
    "name": "ur-small",
    "n_users": 3000,
    "n_items": 700,
    "n_behaviours": 160_000,
    "behaviour_shares": {"buy": 0.020, "pv": 0.897, "cart": 0.055,
                         "fav": 0.029},
    "indicators": ["buy", "pv", "cart", "fav"],
    "algorithm": {"app_name": "small", "max_correlators_per_item": 50,
                  "max_events_per_event_type": 5, "max_query_events": 100},
    "downsampling": {"seed": cco.DOWNSAMPLE_SEED},
    "events": {"lognormal_sigma": 1.0, "popularity_exponent": 0.5},
}


def train_small(cfg: dict, seed: int):
    """(events, the persisted model read back) of one `run_train` job
    through the benchmark's in-memory UR data source."""
    from predictionio_tpu.controller.persistent import deserialize_models

    events, inst, storage = run_small(cfg, seed)
    blob = storage.get_model_data_models().get(inst.id).models
    return events, deserialize_models(blob)[0]


def run_small(cfg: dict, seed: int):
    """(events, the COMPLETED instance, its storage) of one `run_train`
    job through the benchmark's in-memory UR data source."""
    from predictionio_tpu.workflow.core import run_train

    events = ur_train_data.make_events(cfg, seed)
    items = BiMap({f"i{i}": i for i in range(cfg["n_items"])})
    users = BiMap({f"u{u}": u for u in range(cfg["n_users"])})
    ur_train_data.CORPORA[cfg["name"]] = ur_train_data.training_data(
        cfg, events, items, users)
    storage = Storage(StorageConfig(
        sources={"MEM": SourceConfig("MEM", "memory", {})},
        repositories={"METADATA": "MEM", "EVENTDATA": "MEM",
                      "MODELDATA": "MEM"}))
    try:
        inst = run_train(storage, ur_train_data.variant_of(cfg))
    finally:
        ur_train_data.CORPORA.clear()
    assert inst.status == "COMPLETED"
    return events, inst, storage


#: the caps the parity runs at: 5, where users and items are over it by
#: far; 12, where an item's candidates outnumber its 50 places and tie at
#: the 50th
CAPS = [5, 12]


@pytest.fixture(scope="module", params=CAPS, ids=[f"cap{c}" for c in CAPS])
def small(request):
    return dict(SMALL, algorithm=dict(
        SMALL["algorithm"], max_events_per_event_type=request.param))


@pytest.fixture(scope="module")
def trained(small):
    return train_small(small, 2147483701)


@pytest.fixture(scope="module")
def reference(small, trained):
    return ref.Reference(small, trained[0])


def test_the_data_has_the_cases_the_issue_names(small, trained, reference):
    events, _model = trained
    buys = np.bincount(events["buy"][0], minlength=SMALL["n_users"])
    assert (buys == 0).sum() > 100  # users with no buys
    kept_buyers = np.bincount(reference.kept["buy"][1],
                              minlength=SMALL["n_items"])
    assert (kept_buyers == 1).sum() > 10  # items bought once
    # the cap binds on users and on items
    for name in SMALL["indicators"]:
        r, c = ref.distinct(*events[name], SMALL["n_items"])
        cap = small["algorithm"]["max_events_per_event_type"]
        if name != "buy":
            assert np.bincount(r).max() > cap
        assert np.bincount(c).max() > cap
        assert reference.kept[name][1].size < c.size or name == "buy"


@pytest.mark.parametrize("name", SMALL["indicators"])
def test_every_row_matches_the_reference(small, trained, reference, name):
    _events, model = trained
    m = {x.name: x for x in model.indicator_models}[name]
    items = np.arange(SMALL["n_items"])
    assert m.correlator_idx.shape == (SMALL["n_items"], 50)
    rows = {name: {int(i): reference.row(name, int(i)) for i in items}}
    got = ref.compare_rows(rows, {name: (items, m.correlator_idx,
                                         m.correlator_scores)}, 50)
    assert got == {"cco_score_gap": pytest.approx(got["cco_score_gap"]),
                   "cco_set_gap": 0.0, "cco_count_gap": 0.0,
                   "cco_malformed_rows": 0.0}
    assert got["cco_score_gap"] <= 1e-5
    if name == "buy":  # the diagonal is no correlator
        assert not (m.correlator_idx == items[:, None]).any()
    # ties at the 50th place: some full row's 50th and 51st candidates
    # score the same
    tied = [i for i in items if len(rows[name][i][1]) > 50
            and rows[name][i][1][49] == rows[name][i][1][50]]
    if name == "pv" and small["algorithm"]["max_events_per_event_type"] > 5:
        assert tied


def test_with_the_cap_above_every_count_the_sparse_path_is_the_dense_one():
    cfg = dict(SMALL, n_users=400, n_items=120, n_behaviours=12_000,
               algorithm=dict(SMALL["algorithm"],
                              max_events_per_event_type=10**6,
                              max_correlators_per_item=8))
    events, model = train_small(cfg, 77)
    buy = events["buy"]
    primary = cco.edges_to_indicator(*buy, cfg["n_users"], cfg["n_items"])
    for m in model.indicator_models:
        sec = cco.edges_to_indicator(*events[m.name], cfg["n_users"],
                                     cfg["n_items"])
        vals, idx = cco.cross_occurrence_topn(
            primary, sec, 8, self_indicator=m.name == "buy")
        np.testing.assert_array_equal(m.correlator_idx, idx)
        live = idx >= 0
        np.testing.assert_allclose(m.correlator_scores[live], vals[live],
                                   rtol=1e-5)


def test_train_builds_no_dense_matrix(monkeypatch):
    def refuse(*_a, **_k):
        raise AssertionError("a dense (users x items) matrix")

    monkeypatch.setattr(cco, "edges_to_indicator", refuse)
    monkeypatch.setattr(cco, "cross_occurrence_topn", refuse)
    _events, model = train_small(dict(SMALL, n_users=500, n_behaviours=20_000),
                                 3)
    assert len(model.indicator_models) == 4


def test_max_events_per_event_type_is_read_from_engine_json():
    from predictionio_tpu.controller.engine import resolve_engine
    from predictionio_tpu.controller.params import load_symbol

    variant = ur_train_data.variant_of(SMALL)
    engine = resolve_engine(load_symbol(variant["engineFactory"]))
    params = engine.params_from_variant_json(variant)
    (_name, algo), = params.algorithm_params_list
    assert algo.max_events_per_event_type == 5  # SMALL's
    del variant["algorithms"][0]["params"]["max_events_per_event_type"]
    (_name, algo), = engine.params_from_variant_json(
        variant).algorithm_params_list
    assert algo.max_events_per_event_type == 500  # the engine's default


def test_blocks_of_any_size_give_the_same_tables(monkeypatch):
    """The pairs cut into many small blocks — an indicator over several,
    a block over several indicators — place the same tables as one
    block."""
    cfg = SMALL
    events = ur_train_data.make_events(cfg, 23)
    kept = [cco.downsample(cco.group_by_user(
        *events[name], cfg["n_users"], cfg["n_items"]), 5,
        cco.DOWNSAMPLE_SEED, m) for m, name in enumerate(cfg["indicators"])]
    one, one_stats = cco.join_indicators(kept[0], kept, cfg["n_users"], 50,
                                         self_first=True)
    monkeypatch.setattr(cco, "BLOCK_PAIRS", 1024)
    many, stats = cco.join_indicators(kept[0], kept, cfg["n_users"], 50,
                                      self_first=True)
    assert len(one_stats["blocks"]) == 1
    assert len(stats["blocks"]) > 2 * len(kept)
    assert any(len(b["indicators"]) > 1 for b in stats["blocks"])
    assert all(b["pairs"] <= 1024 for b in stats["blocks"])
    assert stats["pairs"] == one_stats["pairs"]
    for (s1, i1), (s2, i2) in zip(one, many):
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_array_equal(s1, s2)


# -- the layout pipeline: chunks on worker threads, one block ahead ------------


def kept_small(seed: int = 23) -> list:
    """SMALL's four indicators grouped and downsampled at cap 5: the
    primary (buy) first."""
    events = ur_train_data.make_events(SMALL, seed)
    return [cco.downsample(cco.group_by_user(
        *events[name], SMALL["n_users"], SMALL["n_items"]), 5,
        cco.DOWNSAMPLE_SEED, m) for m, name in enumerate(SMALL["indicators"])]


def join_small(kept: list):
    return cco.join_indicators(kept[0], kept, SMALL["n_users"], 50,
                               self_first=True)


def pool_threads() -> list:
    return [t.name for t in threading.enumerate()
            if t.name.startswith("ur-layout")]


@pytest.mark.parametrize("workers", [1, 2, 8])
def test_chunks_on_any_number_of_workers_place_the_tables_of_one_block(
        monkeypatch, workers):
    """Many blocks, several chunks each, a block over several indicators,
    laid out on 1, 2 or 8 threads: the tables of one whole block, bit for
    bit, and the stats of the same blocks laid out whole."""
    kept = kept_small()
    one, one_stats = join_small(kept)
    monkeypatch.setattr(cco, "BLOCK_PAIRS", 1024)
    whole, whole_stats = join_small(kept)
    calls = []
    lay_out = cco._Layout.lay_out

    def counted(self, buf, chunk):
        calls.append(chunk)
        lay_out(self, buf, chunk)

    monkeypatch.setattr(cco._Layout, "lay_out", counted)
    monkeypatch.setattr(cco, "_LAYOUT_CHUNK_PAIRS", 100)
    monkeypatch.setattr(cco, "_LAYOUT_WORKERS", workers)
    many, stats = join_small(kept)
    assert len(one_stats["blocks"]) == 1
    assert len(stats["blocks"]) > 2 * len(kept)
    assert any(len(b["indicators"]) > 1 for b in stats["blocks"])
    assert len(calls) > 4 * len(stats["blocks"])
    assert stats == whole_stats
    assert stats["pairs"] == one_stats["pairs"]
    for (s1, i1), (s2, i2) in zip(one, many):
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_array_equal(s1, s2)
    assert not pool_threads()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_blocks_chunks_lay_out_what_the_whole_block_does(seed):
    """Random lens (users with no secondary events among them): the
    chunks, laid out in any order, write the slots one whole `lay_out`
    writes, in contiguous and disjoint spans."""
    rng = np.random.default_rng(seed)
    n_users, n_items, n_things = 400, 60, 90
    primary = cco.group_by_user(rng.integers(0, n_users, 3000),
                                rng.integers(0, n_items, 3000), n_users,
                                n_items)
    # a third of the users have no secondary events
    busy = rng.permutation(n_users)[: 2 * n_users // 3]
    secondary = cco.group_by_user(rng.choice(busy, 5000),
                                  rng.integers(0, n_things, 5000), n_users,
                                  n_things)
    layout = cco._Layout(primary, [secondary])
    lens = layout.lens[0]
    assert (lens == 0).any()
    live = lens > 0
    items = layout.by_item[live]
    whole = cco._PairBlock(int(lens.sum()))
    whole.lay_out(0, items, layout.prim_totals[items],
                  secondary.ptr[layout.users[live]], lens[live], secondary,
                  layout.sec_totals[0])
    chunks, block = layout.chunks(0, n_items, 97)
    assert block == {"indicators": [0], "pairs": int(lens.sum())}
    assert len(chunks) > 3
    at = 0
    for _m, e_lo, e_hi, start in chunks:
        assert start == at
        at += int(lens[e_lo:e_hi].sum())
    assert at == block["pairs"]
    chunked = cco._PairBlock(block["pairs"])
    for chunk in rng.permutation(len(chunks)):
        layout.lay_out(chunked, chunks[chunk])
    for name in ("items", "things", "prim_totals", "sec_totals"):
        np.testing.assert_array_equal(getattr(chunked, name),
                                      getattr(whole, name))


def test_the_next_block_is_laid_out_while_the_device_runs_this_one(
        monkeypatch):
    """Block n + 1's `ur.train.lay_out` starts before block n's
    `_pair_counts_jit` returns, so before its `_llr_topn_jit` does, and a
    block is handed over only once the block before it has been counted:
    never two ahead."""
    from predictionio_tpu.obs import spans

    kept = kept_small()
    monkeypatch.setattr(cco, "BLOCK_PAIRS", 1024)
    llr_runs, counted, handed = [], [], []
    llr_topn, pair_counts, hand_over = (
        cco._llr_topn_jit, cco._pair_counts_jit, cco._hand_over)

    def slow_llr_topn(*args, **kwargs):
        t0 = time.monotonic()
        time.sleep(0.05)
        out = llr_topn(*args, **kwargs)
        llr_runs.append((t0, time.monotonic()))
        return out

    def pair_counts_counted(*args, **kwargs):
        time.sleep(0.05)
        out = pair_counts(*args, **kwargs)
        counted.append(time.monotonic())
        return out

    def hand_over_noted(buf):
        handed.append(len(counted))
        return hand_over(buf)

    monkeypatch.setattr(cco, "_llr_topn_jit", slow_llr_topn)
    monkeypatch.setattr(cco, "_pair_counts_jit", pair_counts_counted)
    monkeypatch.setattr(cco, "_hand_over", hand_over_noted)
    t0 = time.monotonic()
    _tables, stats = join_small(kept)
    n = len(stats["blocks"])
    assert n > 4 and len(llr_runs) == n
    # block k is handed over once blocks up to k - 1 are counted, no later
    # than block k - 1's scoring
    assert handed == list(range(n))
    lay_outs = sorted(
        (sp for sp in spans.get_default_recorder().recent()
         if sp.name == "ur.train.lay_out" and sp.start_mono >= t0),
        key=lambda sp: sp.start_mono)
    assert len(lay_outs) == n
    for k in range(n - 1):
        assert lay_outs[k + 1].start_mono < counted[k] < llr_runs[k][1]
    assert all(sp.attrs["workers"] == cco._LAYOUT_WORKERS
               and sp.attrs["chunks"] >= 1 for sp in lay_outs)
    assert [sp.attrs["pairs"] for sp in lay_outs] == [
        b["pairs"] for b in stats["blocks"]]


class LayoutFault(Exception):
    pass


@pytest.mark.parametrize("where", ["layout worker", "caller"])
def test_an_error_comes_out_of_the_join_and_no_thread_outlives_it(
        monkeypatch, where):
    kept = kept_small()
    monkeypatch.setattr(cco, "BLOCK_PAIRS", 1024)
    monkeypatch.setattr(cco, "_LAYOUT_CHUNK_PAIRS", 100)
    calls = []

    def fail_in_layout(self, buf, chunk):
        calls.append(chunk)
        if len(calls) == 12:
            raise LayoutFault("a chunk failed")

    def fail_in_scoring(*_args, **_kwargs):
        raise LayoutFault("a program failed")

    if where == "caller":
        monkeypatch.setattr(cco, "_llr_topn_jit", fail_in_scoring)
    else:
        monkeypatch.setattr(cco._Layout, "lay_out", fail_in_layout)
    with pytest.raises(LayoutFault) as err:
        join_small(kept)
    faulty = "fail_in_scoring" if where == "caller" else "fail_in_layout"
    assert any(entry.name == faulty for entry in err.traceback)
    assert not pool_threads()


def test_a_job_times_its_layout_beside_the_join_and_counts_every_block(
        monkeypatch):
    """`run_train`: the workers' span is in the job's timings, the job's
    unattributed time stays >= 0, and the counter counts each block once,
    ahead or waited."""
    monkeypatch.setattr(cco, "BLOCK_PAIRS", 1024)
    joined = []
    join = cco.join_indicators

    def noted(*args, **kwargs):
        out = join(*args, **kwargs)
        joined.append(out[1])
        return out

    monkeypatch.setattr(cco, "join_indicators", noted)
    counter = cco._LAYOUT_BLOCKS
    before, waited = counter.total, counter.value(ready="waited")
    _events, inst, _storage = run_small(
        dict(SMALL, n_users=500, n_behaviours=20_000), 3)
    timings = json.loads(inst.env["stage_timings"])
    assert timings["ur.train.lay_out"] > 0
    assert timings["unattributed"] >= 0
    blocks = len(joined[0]["blocks"])
    assert blocks > 1
    assert counter.total - before == blocks
    assert counter.value(ready="waited") - waited >= 1  # the first block
