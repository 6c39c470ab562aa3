"""The sharded serving tier against the benchmark's plain reference
(ISSUE 27): that a sharded answer equals the unsharded reference is the
point of the `als-webgraph-desparse-d128.serve-sharded` cell, so the same
comparison runs here at a small size on the suite's forced host devices —
`ShardedRuntime` over 4 shards and the engine's `_predict_batch` with
`shard_serving`, against `benchmarks/reference/topk_scores.py` (f32,
`Precision.HIGHEST`, exact top-`num` over the WHOLE catalogue less the
blacklist, nothing imported from the program), under the cell's own limits.

The item count is not a multiple of 512, users come from every shard,
blacklists are row lists whose ids fall in every shard and in the pad, and
`num` is larger than the last shard's live rows.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

if len(jax.devices()) < 8:  # pragma: no cover - env guard
    pytest.skip(
        "needs 8 devices (xla_force_host_platform_device_count)",
        allow_module_level=True,
    )

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import topk_scores as ref  # noqa: E402
from predictionio_tpu.models import als  # noqa: E402
from predictionio_tpu.obs import spans as _spans  # noqa: E402
from predictionio_tpu.parallel.mesh import serving_mesh  # noqa: E402

N_USERS, N_ITEMS, RANK = 1003, 1321, 16
SHARDS = 4
CELL = "als-webgraph-desparse-d128.serve-sharded"
with open(os.path.join(ROOT, "benchmarks", "workloads", CELL + ".json")) as f:
    LIMITS = json.load(f)["limits"]

#: one user from every shard's slab (251 rows a shard) and its edges
USERS = np.array([0, 250, 251, 502, 600, 753, 1002, 7], np.int64)
#: ids in every item shard (XLA path: 352 rows a shard; fused: 384), next
#: to a shard's edge, and in the pad (>= N_ITEMS), which must be inert
BLACK = [
    [],
    [0, 351, 352, 383, 384, 767, 768, 1320],
    [5],
    [N_ITEMS, N_ITEMS + 50, 1319],
    [],
    [1056, 1152, 1200, 1300],
    [351, 703, 1055],
    [],
]


@pytest.fixture(scope="module")
def tables():
    rng = np.random.default_rng(27)
    scale = np.float32(1.0 / np.sqrt(RANK))
    uf = rng.standard_normal((N_USERS, RANK), dtype=np.float32) * scale
    itf = rng.standard_normal((N_ITEMS, RANK), dtype=np.float32) * scale
    return uf, itf


def rowlist(lists, width=8):
    out = np.full((len(lists), width), -1, np.int32)
    for i, ids in enumerate(lists):
        out[i, : len(ids)] = ids
    return out


def gaps(user_rows, served_rows, served_scores, black, num, uf, itf):
    """The comparison that decides the cell's `correct`
    (`benchmarks/serving.py` `compare_sample`), on arrays."""
    want, scale = ref.served_scores(user_rows, served_rows, uf, itf)
    live = [[r for r in b if r < itf.shape[0]] for b in black]
    kth = ref.kth_best_allowed(user_rows, live, num, uf, itf, block=512)
    rel = np.abs(served_scores - want) / scale
    return {
        "score_gap": float(rel.max()),
        "score_rms_gap": float(np.sqrt(np.mean(rel ** 2))),
        "rank_gap": float(np.max(
            (kth - want.min(axis=1)) / scale.max(axis=1)).clip(min=0.0)),
    }


@pytest.fixture(scope="module", params=["xla", "fused"])
def runtime(request, tables):
    from predictionio_tpu.fleet import ShardedRuntime

    uf, itf = tables
    mode = "interpret" if request.param == "fused" else "off"
    srt = ShardedRuntime(uf, itf, mesh=serving_mesh(SHARDS), serve_mode=mode)
    assert srt.n_shards == SHARDS
    return srt


# num = 10 is the cell's; 300 is more than the last shard's live rows
# (1321 - 3 * 352 = 265 on the XLA path, 1321 - 3 * 384 = 169 fused)
@pytest.mark.parametrize("num", [10, 300])
@pytest.mark.parametrize("form", ["none", "rows"])
def test_sharded_runtime_equals_the_unsharded_reference(
        runtime, tables, form, num):
    uf, itf = tables
    black = BLACK if form == "rows" else [[] for _ in USERS]
    scores, items = runtime.recommend(
        USERS, num, exclude_rows=rowlist(black) if form == "rows" else None)
    assert scores.shape == items.shape == (len(USERS), num)
    for row, b in zip(items, black):
        assert len(set(row.tolist())) == num  # distinct
        assert 0 <= row.min() and row.max() < N_ITEMS  # never a pad row
        assert not set(row.tolist()) & set(b)  # nothing excluded is served
    assert (np.diff(scores, axis=1) <= 0).all()  # best first
    got = gaps(USERS, items, scores, black, num, uf, itf)
    for name, limit in LIMITS.items():
        assert got[name] <= limit, (name, got)
    # on the CPU every product is exact f32: far under the chip's limits
    assert got["score_gap"] < 1e-5 and got["rank_gap"] < 1e-5


def test_sharded_runtime_equals_the_reference_under_a_dense_mask(
        runtime, tables):
    """The mask form (a whitelist's complement) packs to the same words."""
    uf, itf = tables
    allowed = np.arange(3, N_ITEMS, 7)
    mask = np.ones((len(USERS), N_ITEMS), bool)
    mask[:, allowed] = False
    black = [sorted(set(range(N_ITEMS)) - set(allowed.tolist()))] * len(USERS)
    scores, items = runtime.recommend(USERS, 10, exclude_mask=mask)
    assert np.isin(items, allowed).all()
    got = gaps(USERS, items, scores, black, 10, uf, itf)
    for name, limit in LIMITS.items():
        assert got[name] <= limit, (name, got)


@pytest.mark.parametrize("serve_dtype", ["f32", "int8"])
def test_engine_shard_serving_against_the_reference(tables, serve_dtype):
    """`_predict_batch` with `shard_serving` (every visible device a
    shard), by ids as a client sees them; the int8 control reads over the
    f32 answer's gap, as it must for the cell's limits to mean anything."""
    from predictionio_tpu.data.store.bimap import BiMap
    from predictionio_tpu.engines.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
        Query,
    )

    uf, itf = tables
    fs = als.ALSFactors(
        uf, itf,
        BiMap({f"u{i}": i for i in range(N_USERS)}),
        BiMap({f"i{i}": i for i in range(N_ITEMS)}),
        als.ALSParams(rank=RANK),
    )
    model = ALSModel(fs, serve_dtype=serve_dtype)
    algo = ALSAlgorithm(ALSAlgorithmParams(
        rank=RANK, shard_serving=True, serve_dtype=serve_dtype))
    black = [[r for r in b if r < N_ITEMS] for b in BLACK]
    queries = [
        Query(user=f"u{u}", num=10, blacklist=[f"i{r}" for r in b] or None)
        for u, b in zip(USERS, black)
    ]
    results = algo._predict_batch(model, queries)
    assert model.sharded_info()["shards"] == len(jax.devices())
    items = np.array([[ref.row_of(s.item, "i", N_ITEMS)
                       for s in r.item_scores] for r in results])
    scores = np.array([[s.score for s in r.item_scores] for r in results])
    assert items.shape == (len(USERS), 10) and items.min() >= 0
    for row, b in zip(items, black):
        assert not set(row.tolist()) & set(b)
    got = gaps(USERS, items, scores, black, 10, uf, itf)
    if serve_dtype == "f32":
        for name, limit in LIMITS.items():
            assert got[name] <= limit, (name, got)
        assert got["score_gap"] < 1e-5
    else:
        assert got["score_rms_gap"] > LIMITS["score_rms_gap"], got


def test_recommend_records_its_three_spans_and_moves_both_counters(tables):
    """Profiler off: `sharded.pack_exclusions`, `sharded.dispatch` (with its
    children `.put` and `.release`) and `sharded.copy_back` with their
    attrs, and the two counters through the bridge a `QueryServer` mounts on
    its registry."""
    from predictionio_tpu.fleet import ShardedRuntime, bridge_sharded_metrics
    from predictionio_tpu.obs.registry import MetricsRegistry

    uf, itf = tables
    srt = ShardedRuntime(uf, itf, mesh=serving_mesh(SHARDS))
    recorder = _spans.get_default_recorder()
    registry = MetricsRegistry()
    bridge = bridge_sharded_metrics(registry)
    try:
        t0 = time.time()
        srt.recommend(USERS[:2], 10)
        srt.recommend(USERS, 10, exclude_rows=rowlist(BLACK))
        mask = np.zeros((1, N_ITEMS), bool)
        mask[0, :100] = True
        srt.recommend(USERS[:1], 10, exclude_mask=mask)
    finally:
        recorder.unbridge("sharded.dispatch", bridge)
    mine = [s for s in recorder.recent(t0) if s.name.startswith("sharded.")]
    by_name: dict[str, list] = {}
    for s in mine:
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["sharded.dispatch"]) == 3
    assert len(by_name["sharded.copy_back"]) == 3
    assert len(by_name["sharded.pack_exclusions"]) == 2  # none packs nothing
    i_p = int(srt._state.itf.shape[0])
    packs = sorted(by_name["sharded.pack_exclusions"],
                   key=lambda s: s.attrs["rows"])
    assert [(s.attrs["form"], s.attrs["rows"], s.attrs["bytes"])
            for s in packs] == [
        ("mask", 1, 1 * i_p // 32 * 4), ("rows", 8, 8 * i_p // 32 * 4)]
    dispatches = sorted(by_name["sharded.dispatch"],
                        key=lambda s: s.attrs["batch"])
    assert [(s.attrs["batch"], s.attrs["shards"], s.attrs["form"])
            for s in dispatches] == [
        (1, SHARDS, "mask"), (2, SHARDS, "none"), (8, SHARDS, "rows")]
    # inside a dispatch: the puts until resident (query rows + words),
    # and the words' release where there were any
    puts = sorted(by_name["sharded.dispatch.put"],
                  key=lambda s: s.attrs["bytes"])
    assert [s.attrs["bytes"] for s in puts] == [
        2 * 4, 1 * 4 + 1 * i_p // 32 * 4, 8 * 4 + 8 * i_p // 32 * 4]
    assert len(by_name["sharded.dispatch.release"]) == 2
    own = {s.span_id for s in dispatches}
    for child in puts + by_name["sharded.dispatch.release"]:
        assert child.parent_span_id in own
    assert all(s.duration > 0 for s in mine)
    batches = registry.counter("sharded_batches_total", labelnames=("form",))
    assert [batches.value(form=f) for f in ("none", "rows", "mask")] == [1, 1, 1]
    assert registry.counter("sharded_exclusion_bytes_total").total == \
        9 * i_p // 32 * 4


def test_staging_records_the_stage_spans(tables):
    from predictionio_tpu.fleet import ShardedRuntime

    uf, itf = tables
    t0 = time.time()
    srt = ShardedRuntime(uf, itf, mesh=serving_mesh(SHARDS))
    spans = {s.name: s for s in _spans.get_default_recorder().recent(t0)
             if s.name.startswith("sharded.stage")}
    assert set(spans) == {
        "sharded.stage", "sharded.stage.pad", "sharded.stage.transfer"}
    stage = spans["sharded.stage"]
    for child in ("sharded.stage.pad", "sharded.stage.transfer"):
        assert spans[child].parent_span_id == stage.span_id
    assert spans["sharded.stage.transfer"].attrs["bytes"] == \
        srt.device_bytes()["total"]
    assert stage.attrs["shards"] == SHARDS


def test_a_state_over_one_devices_budget_names_the_sharded_tier(
        tables, monkeypatch):
    """With `shard_serving` off and a factor state over the device's
    budget, staging refuses by name instead of dying in the allocator."""
    from predictionio_tpu.engines.recommendation.engine import ALSModel
    from predictionio_tpu.fleet import OversizedModelError

    uf, itf = tables
    fs = als.ALSFactors(uf, itf, None, None, als.ALSParams(rank=RANK))
    need = (N_USERS + N_ITEMS) * RANK * 4
    monkeypatch.setenv("PIO_SERVE_HBM_BYTES", str(need - 1))
    with pytest.raises(OversizedModelError, match="shard_serving"):
        ALSModel(fs).serving_state()
    # int8 slabs are a quarter of the bytes: the same budget holds them
    assert ALSModel(fs, serve_dtype="int8").serving_state() is not None
    monkeypatch.setenv("PIO_SERVE_HBM_BYTES", str(need))
    assert ALSModel(fs).serving_state() is not None
