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

Since ISSUE 29 a row list of at most `ROWLIST_MAX` ids a query crosses the
shards as ids and each shard renumbers it into its own slab's rows; the
same lists shipped as packed words (one column wider than the kernel takes)
and the one-chip tier's `als.recommend_serving` must give the same answer.
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


@pytest.fixture(scope="module")
def tiers(tables):
    """(ShardedRuntime over 4 shards, the one-chip tier's staged state) by
    (path, serve_dtype), each staged once for the module."""
    from predictionio_tpu.fleet import ShardedRuntime

    uf, itf = tables
    fs = als.ALSFactors(uf, itf, None, None, als.ALSParams(rank=RANK))
    staged: dict = {}

    def get(path, serve_dtype):
        if (path, serve_dtype) not in staged:
            mode = "interpret" if path == "fused" else "off"
            staged[path, serve_dtype] = (
                ShardedRuntime(uf, itf, mesh=serving_mesh(SHARDS),
                               serve_mode=mode, serve_dtype=serve_dtype),
                als.stage_serving(fs, serve_dtype=serve_dtype, mode=mode),
            )
        return staged[path, serve_dtype]

    return get


def spy_on_pack_rows(monkeypatch, srt) -> list:
    """The shapes of the row lists `srt` packs to (B, I_p/32) words from
    here on: empty for as long as every list crosses as ids."""
    packed: list = []
    pack_rows = srt._pack_rows
    monkeypatch.setattr(
        srt, "_pack_rows", lambda ex: packed.append(ex.shape) or pack_rows(ex))
    return packed


def awkward_lists(users, width, i_local, i_p, uf, itf):
    """A blacklist a query, of at most `width` ids, four kinds in turn: the
    first and last live row of every shard; what the user would be served,
    one id twice, ids in the last shard's pad and past the table; nothing
    but the pad value; `width` ids and no pad. Wider lists fill with more
    of what would be served, so that the exclusion decides the answer."""
    rng = np.random.default_rng(29)
    lists = []
    for n, u in enumerate(users):
        best = np.argsort(-(itf @ uf[u]))[: max(3, width // 2)].tolist()
        kind = n % 4
        if kind == 0:
            ids = [r for s in range(SHARDS)
                   for r in (s * i_local,
                             min((s + 1) * i_local, N_ITEMS) - 1)]
            ids += best[: width - len(ids)]
        elif kind == 1:
            ids = best[:3] + [best[0], N_ITEMS, i_p - 1, i_p, 2 ** 31 - 1]
            ids += best[3: 3 + width - len(ids)]
        elif kind == 2:
            ids = []
        else:
            ids = rng.choice(N_ITEMS, width, replace=False).tolist()
        lists.append(ids)
    return lists


# B = 1, 8, 64 are the serving bucket ladder; E = 8 is what `rowlist_np`
# gives every list of 1-8 ids, 64 the widest the kernel's chain takes
@pytest.mark.parametrize("serve_dtype", ["f32", "int8"])
@pytest.mark.parametrize("path", ["xla", "fused"])
@pytest.mark.parametrize("width", [8, 64])
@pytest.mark.parametrize("batch", [1, 8, 64])
def test_a_row_list_crosses_as_ids_and_answers_as_words_and_one_chip_do(
        tiers, tables, monkeypatch, batch, width, path, serve_dtype):
    uf, itf = tables
    srt, one_chip = tiers(path, serve_dtype)
    i_p = int(srt._state.itf.shape[0])
    # every kind of list at every batch size: B = 1 serves four batches
    n = max(batch, 4)
    users = np.random.default_rng(batch).choice(N_USERS, n, replace=False)
    black = awkward_lists(users, width, i_p // SHARDS, i_p, uf, itf)
    packed = spy_on_pack_rows(monkeypatch, srt)
    for lo in range(0, n, batch):
        rows = users[lo: lo + batch]
        lists = black[lo: lo + batch]
        ex = rowlist(lists, width)
        scores, items = srt.recommend(rows, 10, exclude_rows=ex)
        assert not packed  # ids crossed: no (B, I_p/32) words were built
        # one pad column more than the kernel takes: the same lists as words
        wide = np.pad(ex, ((0, 0), (0, 65 - width)), constant_values=-1)
        w_scores, w_items = srt.recommend(rows, 10, exclude_rows=wide)
        assert packed.pop() and not packed
        o_scores, o_items = als.recommend_serving(
            one_chip, rows, 10, exclude_rows=ex)
        for got_items, got_scores in ((w_items, w_scores),
                                      (o_items, o_scores)):
            np.testing.assert_array_equal(items, got_items)
            np.testing.assert_allclose(
                scores, got_scores, rtol=1e-5, atol=1e-6)
        for row, b in zip(items, lists):
            assert len(set(row.tolist())) == 10
            assert 0 <= row.min() and row.max() < N_ITEMS
            assert not set(row.tolist()) & set(b)
        if serve_dtype == "f32":  # int8 is the cell's control: it must not
            got = gaps(rows, items, scores, lists, 10, uf, itf)
            for name, limit in LIMITS.items():
                assert got[name] <= limit, (name, got)
            assert got["score_gap"] < 1e-5 and got["rank_gap"] < 1e-5


#: a catalogue large enough that `pad_items` takes a tile above 128
#: (ISSUE 31): 16,498 rows a shard pad to 65 tiles of 256, 16,640, where
#: the next multiple of 128 was 16,512 — every shard boundary moves, and
#: the last shard holds 16,070 live rows under 570 of pad
WIDE_ITEMS, WIDE_SLAB, WIDE_RANK = 65_990, 16_640, 8
WIDE_EDGES = [s * WIDE_SLAB for s in range(1, SHARDS)]


@pytest.fixture(scope="module")
def wide():
    """Tables whose answers the new boundaries decide: every item entry is
    positive, so user 0 (all positive) is served the largest rows and user
    1 (all negative) the smallest, every one of its live scores under the
    0 a pad row would score; the rows on each side of every shard boundary
    and the last live row are the largest, the next ones out the smallest.
    Users 2.. are plain."""
    from predictionio_tpu.fleet import ShardedRuntime

    rng = np.random.default_rng(31)
    scale = np.float32(1.0 / np.sqrt(WIDE_RANK))
    uf = rng.standard_normal((64, WIDE_RANK), dtype=np.float32) * scale
    uf[0], uf[1] = np.abs(uf[0]), -np.abs(uf[1])
    itf = np.abs(
        rng.standard_normal((WIDE_ITEMS, WIDE_RANK), dtype=np.float32)
    ) * scale
    large = [r for e in WIDE_EDGES for r in (e - 1, e)] + [WIDE_ITEMS - 1]
    small = [r for e in WIDE_EDGES for r in (e - 2, e + 1)] + [WIDE_ITEMS - 2]
    itf[large] = 4.0 * scale * np.linspace(1.0, 1.3, len(large))[:, None]
    itf[small] = 0.01 * scale * np.linspace(1.0, 1.3, len(small))[:, None]
    srt = ShardedRuntime(
        uf, itf, mesh=serving_mesh(SHARDS), serve_mode="interpret")
    return uf, itf, srt, large, small


def test_the_pad_rule_takes_a_wider_tile_and_moves_the_boundaries(wide):
    from predictionio_tpu.ops import recommend_pallas as rp

    uf, itf, srt, large, small = wide
    info = srt.info()
    assert info["item_rows_padded"] == SHARDS * WIDE_SLAB
    assert info["item_tile"] == 256 == rp.pick_item_tile(WIDE_SLAB)
    assert int(srt._state.itf.shape[0]) == SHARDS * WIDE_SLAB
    # unfiltered, the planted rows ARE the answers: the test below decides
    # something. User 1's live scores are all negative; a pad row scores 0
    scores, items = srt.recommend(np.array([0, 1]), 7)
    assert sorted(items[0].tolist()) == sorted(large)
    assert sorted(items[1].tolist()) == sorted(small)
    assert (scores[1] < 0).all()


@pytest.mark.parametrize("form", ["rows", "rows64", "words", "mask"])
@pytest.mark.parametrize("batch", [1, 8, 64])
def test_blacklists_across_the_new_boundaries_equal_the_unsharded_answer(
        wide, monkeypatch, batch, form):
    """Blacklisted ids on each side of every new shard boundary, in the
    last live row and in the pad, in each wire form: the answers are the
    one-chip tier's and the reference's, and no pad row is ever served —
    not to the user whose every live score is below a pad row's 0."""
    uf, itf, srt, large, small = wide
    i_p = SHARDS * WIDE_SLAB
    fs = als.ALSFactors(uf, itf, None, None, als.ALSParams(rank=WIDE_RANK))
    one_chip = als.stage_serving(fs, mode="off")
    pad_ids = [WIDE_ITEMS, i_p - 1]  # dead rows of the last shard: inert
    users = np.arange(max(batch, 4))
    black = [large + pad_ids[:1], small + pad_ids[1:]] + [
        (large[n % 7:] + small[: n % 5])[:8] if n % 3 else []
        for n in range(2, len(users))]
    packed = spy_on_pack_rows(monkeypatch, srt)
    for lo in range(0, len(users), batch):
        rows, lists = users[lo: lo + batch], black[lo: lo + batch]
        if form == "mask":
            mask = np.zeros((len(rows), WIDE_ITEMS), bool)
            for m, ids in zip(mask, lists):
                m[[r for r in ids if r < WIDE_ITEMS]] = True
            scores, items = srt.recommend(rows, 10, exclude_mask=mask)
        else:
            width = {"rows": 8, "rows64": 64, "words": 65}[form]
            scores, items = srt.recommend(
                rows, 10, exclude_rows=rowlist(lists, width))
            assert bool(packed) == (form == "words")  # ids, but past 64
            packed.clear()
        o_scores, o_items = als.recommend_serving(
            one_chip, rows, 10, exclude_rows=rowlist(lists))
        np.testing.assert_array_equal(items, o_items)
        np.testing.assert_allclose(scores, o_scores, rtol=1e-5, atol=1e-6)
        for row, b in zip(items, lists):
            assert len(set(row.tolist())) == 10
            assert 0 <= row.min() and row.max() < WIDE_ITEMS  # never a pad row
            assert not set(row.tolist()) & set(b)
        got = gaps(rows, items, scores, lists, 10, uf, itf)
        for name, limit in LIMITS.items():
            assert got[name] <= limit, (name, got)
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


def test_recommend_records_its_three_spans_and_moves_both_counters(
        tables, monkeypatch):
    """Profiler off: `sharded.pack_exclusions`, `sharded.dispatch` (with its
    children `.put` and `.release`) and `sharded.copy_back` with their
    attrs, and the two counters through the bridge a `QueryServer` mounts on
    its registry. `form` is the WIRE form: a row list ships as ids (B * E * 4
    bytes, no words built, nothing to release); a dense mask, and a list one
    id wider than the kernel takes, ship as words."""
    from predictionio_tpu.fleet import ShardedRuntime, bridge_sharded_metrics
    from predictionio_tpu.obs.registry import MetricsRegistry

    uf, itf = tables
    srt = ShardedRuntime(uf, itf, mesh=serving_mesh(SHARDS))
    packed = spy_on_pack_rows(monkeypatch, srt)
    recorder = _spans.get_default_recorder()
    registry = MetricsRegistry()
    bridge = bridge_sharded_metrics(registry)
    try:
        t0 = time.time()
        srt.recommend(USERS[:2], 10)
        srt.recommend(USERS, 10, exclude_rows=rowlist(BLACK))
        assert not packed
        mask = np.zeros((1, N_ITEMS), bool)
        mask[0, :100] = True
        srt.recommend(USERS[:1], 10, exclude_mask=mask)
        _, items = srt.recommend(
            USERS[:4], 10, exclude_rows=rowlist(BLACK[:4], 65))
        assert packed == [(4, 65)]
        for row, b in zip(items, BLACK):
            assert not set(row.tolist()) & set(b)  # words exclude too
    finally:
        recorder.unbridge("sharded.dispatch", bridge)
    mine = [s for s in recorder.recent(t0) if s.name.startswith("sharded.")]
    by_name: dict[str, list] = {}
    for s in mine:
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["sharded.dispatch"]) == 4
    assert len(by_name["sharded.copy_back"]) == 4
    assert len(by_name["sharded.pack_exclusions"]) == 3  # none packs nothing
    words = int(srt._state.itf.shape[0]) // 32 * 4  # bytes a row, packed
    packs = sorted(by_name["sharded.pack_exclusions"],
                   key=lambda s: s.attrs["rows"])
    assert [(s.attrs["form"], s.attrs["rows"], s.attrs["bytes"])
            for s in packs] == [
        ("mask", 1, 1 * words), ("mask", 4, 4 * words), ("rows", 8, 8 * 8 * 4)]
    dispatches = sorted(by_name["sharded.dispatch"],
                        key=lambda s: s.attrs["batch"])
    assert [(s.attrs["batch"], s.attrs["shards"], s.attrs["form"],
             s.attrs["exclusion_bytes"]) for s in dispatches] == [
        (1, SHARDS, "mask", words), (2, SHARDS, "none", 0),
        (4, SHARDS, "mask", 4 * words), (8, SHARDS, "rows", 8 * 8 * 4)]
    # inside a dispatch: the puts until resident (query rows + what ships),
    # and the words' release where words were shipped
    puts = sorted(by_name["sharded.dispatch.put"],
                  key=lambda s: s.attrs["bytes"])
    assert [s.attrs["bytes"] for s in puts] == sorted([
        2 * 4, 1 * 4 + 1 * words, 4 * 4 + 4 * words, 8 * 4 + 8 * 8 * 4])
    assert len(by_name["sharded.dispatch.release"]) == 2
    own = {s.span_id for s in dispatches}
    for child in puts + by_name["sharded.dispatch.release"]:
        assert child.parent_span_id in own
    assert all(s.duration > 0 for s in mine)
    batches = registry.counter("sharded_batches_total", labelnames=("form",))
    assert [batches.value(form=f) for f in ("none", "rows", "mask")] == [1, 1, 2]
    assert registry.counter("sharded_exclusion_bytes_total").total == \
        5 * words + 8 * 8 * 4


@pytest.mark.parametrize("path", ["xla", "fused"])
def test_warmup_compiles_every_program_the_traffic_uses(
        tables, monkeypatch, path):
    """After `ALSAlgorithm.warmup` on a `shard_serving` model, a batch at
    each bucket with no filter, a 1-id and an 8-id blacklist (the row-list
    program, E = 8) and a whitelist (the words program) compiles nothing:
    the benchmark's `compiles_in_window` reads the same counter."""
    from predictionio_tpu.data.store.bimap import BiMap
    from predictionio_tpu.engines.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
        Query,
    )
    from predictionio_tpu.obs import jaxmon

    if path == "fused":
        monkeypatch.setenv("PIO_PALLAS_RECOMMEND", "interpret")
    uf, itf = tables
    fs = als.ALSFactors(
        uf, itf,
        BiMap({f"u{i}": i for i in range(N_USERS)}),
        BiMap({f"i{i}": i for i in range(N_ITEMS)}),
        als.ALSParams(rank=RANK),
    )
    model = ALSModel(fs)
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=RANK, shard_serving=True))
    jaxmon.ensure_compile_listener()
    algo.warmup(model)
    assert model.sharded_info()["serve_mode"] == (
        "interpret" if path == "fused" else "xla")
    rng = np.random.default_rng(31)
    filters = [
        lambda: {},
        lambda: {"blacklist": [f"i{rng.integers(N_ITEMS)}"]},
        lambda: {"blacklist": [f"i{r}" for r in rng.choice(N_ITEMS, 8)]},
        lambda: {"whitelist": [f"i{r}" for r in rng.choice(N_ITEMS, 40)]},
    ]
    t0 = time.time()
    before = jaxmon.compile_snapshot()[0]
    for rows in (1, 8, 64):
        for filt in filters:
            queries = [Query(user=f"u{u}", num=10, **filt())
                       for u in rng.choice(N_USERS, rows)]
            results = algo._predict_batch(model, queries)
            assert all(len(r.item_scores) == 10 for r in results)
    assert jaxmon.compile_snapshot()[0] == before
    # and the traffic's blacklists did cross as ids, the whitelists as words
    shipped = [s.attrs["form"] for s in sorted(
        (s for s in _spans.get_default_recorder().recent(t0)
         if s.name == "sharded.dispatch"), key=lambda s: s.start)]
    assert shipped == ["none", "rows", "rows", "mask"] * 3


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
    # what the pad rule decided rides the span and `info()` (ISSUE 31)
    info = srt.info()
    assert stage.attrs["item_rows_padded"] == info["item_rows_padded"] == \
        int(srt._state.itf.shape[0])
    assert stage.attrs["item_tile"] == info["item_tile"]


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
        ALSModel(fs).resident.get()
    # int8 slabs are a quarter of the bytes: the same budget holds them
    assert ALSModel(fs, serve_dtype="int8").resident.get() is not None
    monkeypatch.setenv("PIO_SERVE_HBM_BYTES", str(need))
    assert ALSModel(fs).resident.get() is not None
