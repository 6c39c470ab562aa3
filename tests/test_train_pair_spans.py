"""The job's one sort, named from inside (ISSUE 37): `_group_unique_pairs`'
three phases as child spans of whichever span groups the pairs, and the
gate's scan for the int8 scale beside them, so that `als.train.dense_eligible`
keeps nothing of size to itself and a job's `stage_timings` carry each."""

from __future__ import annotations

import time

import numpy as np
import pytest

import predictionio_tpu.obs.spans as spans
from predictionio_tpu.models import als

PHASES = ("als.train.pair_key", "als.train.pair_sort", "als.train.pair_group")


def _coo(n_users=300, n_items=180, n_edges=6000, seed=3):
    rng = np.random.RandomState(seed)
    rows = rng.randint(0, n_users, n_edges).astype(np.int32)
    cols = rng.randint(0, n_items, n_edges).astype(np.int32)
    _, idx = np.unique(rows.astype(np.int64) * n_items + cols,
                       return_index=True)
    rows, cols = rows[idx], cols[idx]
    vals = (rng.randint(1, 11, len(rows)) / 2.0).astype(np.float32)
    return rows, cols, vals


def _recorded(since):
    out: dict[str, list] = {}
    for sp in spans.get_default_recorder().recent(since):
        out.setdefault(sp.name, []).append(sp)
    return out


def test_the_gates_sort_is_three_child_spans_and_the_scale_a_fourth(monkeypatch):
    monkeypatch.setenv("PIO_DENSE_ALS", "1")
    rows, cols, vals = _coo()
    before = time.time()
    with spans.collect() as job, spans.span("job"):
        gate = als.dense_eligible(
            rows, cols, vals, 300, 180, als.ALSParams(rank=6, iterations=2))
    assert gate and gate.pairs is not None
    got = _recorded(before)
    [parent] = got["als.train.dense_eligible"]
    children = PHASES + ("als.train.int8_scale",)
    for name in children:
        [sp] = got[name]
        assert sp.parent_span_id == parent.span_id, name
        assert sp.duration > 0.0
        # by name in what a job's stage_timings are made from
        assert job.seconds[name] == pytest.approx(sp.duration)
    key, sort, group = (got[n][0] for n in PHASES)
    assert key.start <= sort.start <= group.start
    covered = sum(got[n][0].duration for n in children)
    assert covered <= parent.duration
    # what the gate's span keeps to itself counts as unattributed (at the
    # cell's size: nothing of size, the scan and the sort being named)
    assert job.unattributed >= parent.duration - covered - 1e-6


def test_a_bare_staging_groups_under_its_own_span(monkeypatch):
    monkeypatch.setenv("PIO_DENSE_ALS", "1")
    rows, cols, vals = _coo(seed=4)
    before = time.time()
    als.stage_dense(rows, cols, vals, 300, 180,
                    als.ALSParams(rank=6, iterations=2))
    got = _recorded(before)
    [prep] = got["als.stage.host_prep"]
    for name in PHASES:
        [sp] = got[name]
        assert sp.parent_span_id == prep.span_id, name


def test_duplicate_pairs_end_the_pass_inside_its_span(monkeypatch):
    monkeypatch.setenv("PIO_DENSE_ALS", "1")
    rows, cols, vals = _coo(seed=5)
    rows[7], cols[7] = rows[3], cols[3]
    before = time.time()
    gate = als.dense_eligible(
        rows, cols, vals, 300, 180, als.ALSParams(rank=6, iterations=2))
    assert gate.verdict == "duplicate_pairs"
    got = _recorded(before)
    assert [len(got[n]) for n in PHASES] == [1, 1, 1]
    assert not got["als.train.pair_group"][0].error
