"""Universal fused serving (ISSUE 14): interpret-mode parity for the
fused `similar` and CCO `batch_score_topk` tails against the XLA
two-step, bit-packed vs row-list mask equivalence, bf16/int8 dtype
invariance, sharded serve_dtype staging + donated dirty-row publish,
device-count invariance, per-dtype devprof columns, and pickle
migration for the models that grew serve_dtype fields."""

import dataclasses
import pickle

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from predictionio_tpu.data.store.bimap import BiMap  # noqa: E402
from predictionio_tpu.models import als, cco  # noqa: E402
from predictionio_tpu.ops import recommend_pallas as rp  # noqa: E402
from predictionio_tpu.ops.topk import NEG_INF, masked_top_k  # noqa: E402


def _factors(rng, u=50, i=300, k=10):
    return als.ALSFactors(
        user_factors=rng.standard_normal((u, k)).astype(np.float32),
        item_factors=rng.standard_normal((i, k)).astype(np.float32),
        user_vocab=BiMap({f"u{n}": n for n in range(u)}),
        item_vocab=BiMap({f"i{n}": n for n in range(i)}),
    )


# ---------------------------------------------------------------------------
# fused similar: exact parity vs the XLA two-step, same score semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
def test_similar_mode_parity(dtype):
    """A mode change never changes `similar` scores within a dtype —
    the fused kernel and the XLA fallback share the scaled-dot cosine
    semantics exactly (indices bit-equal incl. tie order)."""
    rng = np.random.RandomState(20)
    f = _factors(rng)
    sv_i = dataclasses.replace(
        als.stage_serving(f, serve_dtype=dtype), mode="interpret"
    )
    sv_x = dataclasses.replace(sv_i, mode=None)
    v1, i1 = als.similar_serving(sv_i, np.arange(8), 11)
    v0, i0 = als.similar_serving(sv_x, np.arange(8), 11)
    assert np.array_equal(i0, i1)
    np.testing.assert_allclose(v0, v1, rtol=1e-5)
    for r in range(8):  # exclude_self holds on both paths
        assert r not in i1[r]


def test_similar_f32_matches_legacy_similar_items():
    """The fused scaled-dot cosine ranks identically to the legacy
    normalize-then-dot `als.similar_items` (values to f32 rounding)."""
    rng = np.random.RandomState(21)
    f = _factors(rng)
    sv = dataclasses.replace(
        als.stage_serving(f, serve_dtype="f32"), mode="interpret"
    )
    lv, li = als.similar_items(f, np.arange(6), 9)
    nv, ni = als.similar_serving(sv, np.arange(6), 9)
    assert np.array_equal(li, ni)
    np.testing.assert_allclose(lv, nv, rtol=1e-4, atol=1e-5)


def test_similar_cross_tile_ties_and_fully_masked_and_k_eq_n():
    """The ISSUE-named edge cases on the similar verb: duplicated
    cosine scores straddling the 128-row tile boundary keep the
    lax.top_k tie order; a fully-masked row returns NEG_INF at the
    reference order; k == n_items drains the whole list."""
    rng = np.random.RandomState(22)
    base = rng.standard_normal((130, 6)).astype(np.float32)
    itf = np.concatenate([base, base])  # every cosine appears twice
    f = als.ALSFactors(
        np.zeros((0, 6), np.float32), itf, BiMap({}), BiMap({})
    )
    sv_i = dataclasses.replace(
        als.stage_serving(f, serve_dtype="f32"), mode="interpret"
    )
    sv_x = dataclasses.replace(sv_i, mode=None)
    # no exclude_self so the duplicate-row ties actually collide
    v1, i1 = als.similar_serving(sv_i, np.arange(4), 50, exclude_self=False)
    v0, i0 = als.similar_serving(sv_x, np.arange(4), 50, exclude_self=False)
    assert np.array_equal(i0, i1)
    # fully-masked row: everything excluded
    mask = np.zeros((2, 260), bool)
    mask[1, :] = True
    v1, i1 = als.similar_serving(
        sv_i, np.arange(2), 7, exclude_self=False, exclude_mask=mask
    )
    v0, i0 = als.similar_serving(
        sv_x, np.arange(2), 7, exclude_self=False, exclude_mask=mask
    )
    assert np.array_equal(i0, i1)
    assert np.all(v1[1] == NEG_INF)
    # k == n_items
    v1, i1 = als.similar_serving(sv_i, [3], 260, exclude_self=False)
    v0, i0 = als.similar_serving(sv_x, [3], 260, exclude_self=False)
    assert np.array_equal(i0, i1)


def test_packed_vs_rowlist_equivalence():
    """The same exclusion set expressed as bit-packed words and as a
    row list yields identical answers on BOTH kernel modes."""
    rng = np.random.RandomState(23)
    f = _factors(rng)
    ex = np.full((8, 8), -1, np.int32)
    for r in range(8):
        ex[r, :5] = rng.choice(300, 5, replace=False)
    mask = np.zeros((8, 300), bool)
    for r in range(8):
        mask[r, ex[r, :5]] = True
    for mode in ("interpret", None):
        sv = dataclasses.replace(
            als.stage_serving(f, serve_dtype="f32"), mode=mode
        )
        vm, im = als.recommend_serving(
            sv, np.arange(8), 10, exclude_mask=mask
        )
        vr, ir = als.recommend_serving(
            sv, np.arange(8), 10, exclude_rows=ex
        )
        assert np.array_equal(im, ir), mode
        np.testing.assert_allclose(vm, vr, rtol=0)
        assert not np.any(mask[np.arange(8)[:, None], im])


def test_packed_mask_is_one_32th_of_f32_bytes():
    """The acceptance arithmetic: packed words carry exactly 1/32 the
    bytes an f32 0/1 mask of the same padded width would."""
    i_p = rp.pad_items(300)
    mask = np.random.RandomState(0).rand(16, 300) < 0.5
    words = rp.pack_mask_np(mask, i_p)
    assert words.nbytes * 32 == 16 * i_p * 4
    # semantics identical through the traced unpack
    back = np.asarray(rp.unpack_mask_jnp(jnp.asarray(words), 300))
    assert np.array_equal(back, mask)


def test_bf16_serving_halves_factor_bytes_and_is_mode_invariant():
    rng = np.random.RandomState(24)
    f = _factors(rng)
    sv16 = als.stage_serving(f, serve_dtype="bf16")
    sv32 = als.stage_serving(f, serve_dtype="f32")
    assert sv16.items.nbytes * 2 == sv32.items.nbytes
    a = als.recommend_serving(
        dataclasses.replace(sv16, mode="interpret"), np.arange(6), 9
    )
    b = als.recommend_serving(
        dataclasses.replace(sv16, mode=None), np.arange(6), 9
    )
    assert np.array_equal(a[1], b[1])
    np.testing.assert_allclose(a[0], b[0], rtol=1e-5)


# ---------------------------------------------------------------------------
# CCO batch_score_topk fused tail
# ---------------------------------------------------------------------------


def _cco_score(tables, hists, ex, k, mode):
    """`cco.batch_score_topk` as the engine drives it: the tables staged,
    the plan made on the host, the exclusion in the form its ids pick."""
    staged = tables if isinstance(tables, cco.StagedCorrelators) else (
        cco.stage_correlators(tables))
    bsz = hists[0].shape[0]
    exclude = cco.exclusion_of(
        [[int(i) for i in row if i >= 0] for row in ex], bsz,
        staged.rows_padded)
    return cco.batch_score_topk(
        staged, cco.plan_windows(staged, hists), exclude, bsz, k, mode=mode)


def _cco_tables(rng, I=500, T=20, js=(120, 80)):
    tables, hists = [], []
    for J in js:
        idx = rng.randint(-1, J, (I, T)).astype(np.int32)
        sc = np.abs(rng.standard_normal((I, T))).astype(np.float32)
        tables.append((idx, sc, J))
        hists.append(rng.randint(-1, J, (8, 16)).astype(np.int32))
    return tables, hists


@pytest.mark.parametrize("width", [32, 128])
def test_cco_fused_matches_xla_exactly(width):
    """Fused CCO tail == the XLA scatter+where+top_k tail bit-for-bit
    on indices/tie order, for both the row-list (narrow) and the
    host-packed (wide) exclusion forms."""
    rng = np.random.RandomState(25)
    tables, hists = _cco_tables(rng)
    ex = np.full((8, width), -1, np.int32)
    n_ids = 12 if width == 32 else 70  # beyond ROWLIST_MAX: packed words
    for b in range(8):
        ex[b, :n_ids] = rng.choice(500, n_ids, replace=False)
    v0, i0 = _cco_score(tables, hists, ex, 17, "off")
    v1, i1 = _cco_score(tables, hists, ex, 17, "interpret")
    assert np.array_equal(i0, i1)
    np.testing.assert_allclose(v0, v1, rtol=1e-6)


def test_cco_fused_ties_and_k_edge():
    """Crafted equal LLR sums across the tile boundary + k == n_items:
    the fused tail keeps lax.top_k's lowest-index tie order."""
    rng = np.random.RandomState(26)
    I, J = 256, 40
    # every item row carries the SAME correlator set → global ties
    idx = np.tile(rng.randint(0, J, (1, 6)), (I, 1)).astype(np.int32)
    sc = np.tile(
        np.abs(rng.standard_normal((1, 6))), (I, 1)
    ).astype(np.float32)
    hist = rng.randint(-1, J, (4, 8)).astype(np.int32)
    ex = np.full((4, 8), -1, np.int32)
    v0, i0 = _cco_score([(idx, sc, J)], [hist], ex, I, "off")
    v1, i1 = _cco_score([(idx, sc, J)], [hist], ex, I, "interpret")
    assert np.array_equal(i0, i1)
    np.testing.assert_allclose(v0, v1, rtol=1e-6)


def test_cco_host_reference_agreement_fused():
    """The fused path still matches the host reference scorer the XLA
    path is tested against (score_history)."""
    rng = np.random.RandomState(27)
    tables, hists = _cco_tables(rng, I=200, js=(60,))
    ex = np.full((8, 16), -1, np.int32)
    vals, idx = _cco_score(tables, hists, ex, 5, "interpret")
    for b in range(3):
        hist = hists[0][b]
        ref = cco.score_history(
            tables[0][0], tables[0][1], hist[hist >= 0]
        )
        order = np.argsort(-ref, kind="stable")[:5]
        assert np.array_equal(idx[b], order)
        np.testing.assert_allclose(vals[b], ref[order], rtol=1e-5)


# ---------------------------------------------------------------------------
# sharded tier: serve_dtype staging + donated dirty-row publish
# ---------------------------------------------------------------------------


needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the forced multi-device mesh"
)


@needs_mesh
def test_sharded_int8_resident_bytes_about_a_third():
    """Acceptance: int8 staging ≈ 1/3 of f32 resident bytes per shard
    (int8 cells + f32 scale/inverse-norm vectors) at a serving-real
    rank."""
    from predictionio_tpu.fleet.runtime import ShardedRuntime

    rng = np.random.RandomState(30)
    uf = rng.standard_normal((256, 64)).astype(np.float32)
    itf = rng.standard_normal((1024, 64)).astype(np.float32)
    r8 = ShardedRuntime(uf, itf, serve_dtype="int8")
    r32 = ShardedRuntime(uf, itf, serve_dtype="f32")
    ratio = (
        r8.device_bytes()["per_shard"] / r32.device_bytes()["per_shard"]
    )
    assert 0.2 < ratio < 0.4, ratio
    assert r8.info()["serve_dtype"] == "int8"


@needs_mesh
@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_sharded_device_count_invariance(dtype, mode):
    """The same query yields the same answer regardless of shard count
    — for every dtype and both kernel modes, on all three verbs."""
    from predictionio_tpu.fleet.runtime import ShardedRuntime
    from predictionio_tpu.parallel.mesh import serving_mesh

    rng = np.random.RandomState(31)
    uf = rng.standard_normal((40, 8)).astype(np.float32)
    itf = rng.standard_normal((570, 8)).astype(np.float32)
    runtimes = [
        ShardedRuntime(
            uf, itf, serve_dtype=dtype, serve_mode=mode,
            mesh=serving_mesh(n),
        )
        for n in (2, 8)
    ]
    mask = rng.rand(5, 570) < 0.3
    outs = [r.recommend(np.arange(5), 9, exclude_mask=mask) for r in runtimes]
    assert np.array_equal(outs[0][1], outs[1][1])
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-5)
    sims = [r.similar_items(np.arange(4), 7) for r in runtimes]
    assert np.array_equal(sims[0][1], sims[1][1])
    vecs = rng.standard_normal((3, 8)).astype(np.float32)
    vs = [r.similar_vectors(vecs, 6) for r in runtimes]
    assert np.array_equal(vs[0][1], vs[1][1])


@needs_mesh
def test_sharded_int8_matches_single_device_int8():
    """Sharded int8 serving and the single-device int8 staged state
    share quantization semantics exactly (same scales, same int32
    accumulate) — indices bit-equal."""
    from predictionio_tpu.fleet.runtime import ShardedRuntime

    rng = np.random.RandomState(32)
    f = _factors(rng, u=40, i=570, k=8)
    srt = ShardedRuntime(
        f.user_factors, f.item_factors, serve_dtype="int8",
        serve_mode="off",
    )
    sv = dataclasses.replace(
        als.stage_serving(f, serve_dtype="int8"), mode=None
    )
    v0, i0 = als.recommend_serving(sv, np.arange(6), 10)
    v1, i1 = srt.recommend(np.arange(6), 10)
    assert np.array_equal(i0, i1)
    np.testing.assert_allclose(v0, v1, rtol=1e-5)


@needs_mesh
@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_sharded_publish_requantizes_only_dirty_rows(dtype, monkeypatch):
    """Acceptance regression: a fold-in publish into the sharded tier
    re-quantizes/ships ONLY the dirty rows — no full restage (any
    full-matrix staging call after init trips the tripwire), and the
    published rows serve immediately, with fresh cosine norms."""
    from predictionio_tpu.fleet import runtime as rt_mod
    from predictionio_tpu.parallel import mesh as mesh_mod

    rng = np.random.RandomState(33)
    uf = rng.standard_normal((40, 8)).astype(np.float32)
    itf = rng.standard_normal((570, 8)).astype(np.float32)
    srt = rt_mod.ShardedRuntime(uf, itf, serve_dtype=dtype)

    def tripwire(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("full restage attempted after init")

    monkeypatch.setattr(rt_mod, "shard_rows", tripwire)
    monkeypatch.setattr(mesh_mod, "shard_rows", tripwire)
    quant_rows = []
    orig_q = rt_mod._devprof  # keep lint quiet about unused
    import predictionio_tpu.ops.recommend_pallas as rp_mod

    orig_quant = rp_mod.quantize_rows_np

    def spy_quant(arr):
        quant_rows.append(np.asarray(arr).shape[0])
        return orig_quant(arr)

    monkeypatch.setattr(rp_mod, "quantize_rows_np", spy_quant)
    before_v, before_i = srt.recommend([2], 5)
    boost = np.full((2, 8), 9.0, np.float32)
    srt.update_item_rows(np.array([7, 8]), boost)
    srt.update_user_rows(
        np.array([2]), np.full((1, 8), 1.0, np.float32)
    )
    if dtype == "int8":
        # only the dirty rows were quantized: 2 item rows + 1 user row
        assert quant_rows == [2, 1], quant_rows
    _, idx = srt.recommend([2], 2)
    assert set(np.asarray(idx[0])) == {7, 8}
    # fresh inverse norms under similar: the identical boosted rows
    # are each other's nearest neighbors
    s = srt.similar_items(np.array([7]), 1)
    assert s[1][0][0] == 8


@needs_mesh
def test_sharded_publish_zero_drop_under_concurrent_readers():
    """Readers hammering recommend() while publishes land must never
    see an error or a malformed answer — the donated path drains
    leases first and falls back to COW on timeout."""
    import threading

    from predictionio_tpu.fleet.runtime import ShardedRuntime

    rng = np.random.RandomState(34)
    uf = rng.standard_normal((40, 8)).astype(np.float32)
    itf = rng.standard_normal((570, 8)).astype(np.float32)
    srt = ShardedRuntime(uf, itf, serve_dtype="int8")
    errors = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            try:
                v, ix = srt.recommend(np.arange(4), 5)
                assert ix.shape == (4, 5)
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)
                return

    threads = [
        threading.Thread(target=reader, daemon=True) for _ in range(3)
    ]
    for t in threads:
        t.start()
    for i in range(10):
        srt.update_user_rows(
            np.array([i]),
            rng.standard_normal((1, 8)).astype(np.float32),
        )
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors


@needs_mesh
def test_foldin_clone_carries_sharded_runtime():
    """online fold-in → _clone_model publishes the tick's dirty rows
    into the RESIDENT sharded runtime (no restage), and drops the
    carry when a changed side has no row attribution."""
    from predictionio_tpu.engines.recommendation.engine import ALSModel
    from predictionio_tpu.online.foldin import ALSFoldIn

    rng = np.random.RandomState(35)
    f = _factors(rng, u=40, i=570, k=8)
    model = ALSModel(f, serve_dtype="int8")
    from predictionio_tpu.fleet.runtime import ShardedRuntime

    srt = model.resident.get(shard=True)
    assert isinstance(srt, ShardedRuntime)
    solved = rng.standard_normal((2, 8)).astype(np.float32)
    new_uf = f.user_factors.copy()
    new_uf[[1, 2]] = solved
    nf = dataclasses.replace(f, user_factors=new_uf)
    clone = ALSFoldIn._clone_model(
        model, nf, items_changed=False,
        dirty_users=([1, 2], solved),
    )
    assert clone.resident.get(shard=True) is srt
    # the resident runtime serves the folded rows
    ref = ShardedRuntime(
        new_uf, f.item_factors, serve_dtype="int8"
    )
    a = srt.recommend([1], 5)
    b = ref.recommend([1], 5)
    assert np.array_equal(a[1], b[1])
    # a changed side without rows drops the carry
    clone2 = ALSFoldIn._clone_model(model, nf, items_changed=False)
    assert clone2.resident.info() is None


# ---------------------------------------------------------------------------
# engine wiring: itemsim fused cosine + similarproduct staged basket
# ---------------------------------------------------------------------------


def test_itemsim_staged_cosine_matches_legacy_host_path():
    from predictionio_tpu.engines.itemsim.engine import (
        ItemSimAlgorithm,
        ItemSimAlgorithmParams,
        ItemSimModel,
        Query,
    )
    from predictionio_tpu.models import ranking

    rng = np.random.RandomState(36)
    m = (rng.rand(30, 40) < 0.2).astype(np.float32)
    vocab = BiMap({f"i{j}": j for j in range(40)})
    model = ItemSimModel(
        sim_scores=np.zeros((0, 0), np.float32),
        sim_idx=np.zeros((0, 0), np.int64),
        item_vocab=vocab,
        top_n=10,
        item_vectors=np.ascontiguousarray(m.T),
    )
    algo = ItemSimAlgorithm(ItemSimAlgorithmParams(top_n=10))
    got = algo.predict(model, Query(items=["i1", "i3"], num=5))
    # legacy reference: normalize-then-dot + stable argsort
    normed = ranking.l2_normalize(model.item_vectors)
    known = [1, 3]
    scores = normed[known] @ normed.T
    scores[np.arange(2), known] = NEG_INF
    total = np.zeros(40, np.float32)
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :10]
    vals = np.take_along_axis(scores, idx, axis=1)
    for r in range(2):
        ok = vals[r] > NEG_INF / 2
        np.add.at(total, idx[r][ok], vals[r][ok])
    total[known] = 0.0
    top = np.argsort(-total)[:5]
    want = [f"i{ix}" for ix in top if total[ix] > 0.0]
    assert [s.item for s in got.item_scores] == want


def test_itemsim_int8_staged_serving_ranks_sanely():
    from predictionio_tpu.engines.itemsim.engine import (
        ItemSimAlgorithm,
        ItemSimAlgorithmParams,
        ItemSimModel,
        Query,
    )

    rng = np.random.RandomState(37)
    m = (rng.rand(30, 40) < 0.25).astype(np.float32)
    vocab = BiMap({f"i{j}": j for j in range(40)})
    model = ItemSimModel(
        sim_scores=np.zeros((0, 0), np.float32),
        sim_idx=np.zeros((0, 0), np.int64),
        item_vocab=vocab,
        top_n=10,
        item_vectors=np.ascontiguousarray(m.T),
        serve_dtype="int8",
    )
    algo = ItemSimAlgorithm(
        ItemSimAlgorithmParams(top_n=10, serve_dtype="int8")
    )
    got = algo.predict(model, Query(items=["i1"], num=5))
    assert got.item_scores
    assert all(s.item != "i1" for s in got.item_scores)
    assert model.resident.get().dtype == "int8"


def test_similarproduct_staged_basket_matches_host_scores():
    """serve_dtype='f32' forced through the staged verb must reproduce
    the host path's SCORES (the qnorm-multiplied contract), not just
    its ranking."""
    from predictionio_tpu.engines.similarproduct.engine import (
        ALSSimilarAlgorithm,
        ALSSimilarParams,
        Query,
        SimilarModel,
    )

    rng = np.random.RandomState(38)
    f = _factors(rng, u=20, i=60, k=8)
    host = SimilarModel(f, serve_dtype="f32")
    staged = SimilarModel(f, serve_dtype="f32")
    algo_host = ALSSimilarAlgorithm(ALSSimilarParams())
    algo_staged = ALSSimilarAlgorithm(ALSSimilarParams())
    q = Query(items=["i1", "i5"], num=7)
    ref = algo_host._predict(host, q)
    # force the staged route by pretending bf16 staging with f32 data:
    # serve_dtype f32 + CPU resolves the host path, so flip the knob
    algo_staged.params = ALSSimilarParams(serve_dtype="bf16")
    staged.serve_dtype = "f32"  # stage exact factors, fused route
    got = algo_staged._predict(staged, q)
    ref_map = {s.item: s.score for s in ref.item_scores}
    got_map = {s.item: s.score for s in got.item_scores}
    assert set(got_map) == set(ref_map)
    for k_, v in got_map.items():
        assert v == pytest.approx(ref_map[k_], rel=1e-4)


# ---------------------------------------------------------------------------
# pickle migration (models gaining serve_dtype fields)
# ---------------------------------------------------------------------------


def test_similarmodel_pickle_migration():
    from predictionio_tpu.engines.similarproduct.engine import SimilarModel

    rng = np.random.RandomState(40)
    f = _factors(rng, u=10, i=20, k=4)
    m = SimilarModel(f, serve_dtype="int8")
    m2 = pickle.loads(pickle.dumps(m))
    assert m2.serve_dtype == "int8"
    # a pre-ISSUE-14 pickle carried only {"factors": ...}
    legacy = SimilarModel.__new__(SimilarModel)
    legacy.__setstate__({"factors": f})
    assert legacy.serve_dtype == "f32"
    assert legacy.normed_item_factors().shape == (20, 4)


def test_itemsim_pickle_migration():
    from predictionio_tpu.engines.itemsim.engine import ItemSimModel

    vocab = BiMap({"a": 0})
    m = ItemSimModel(
        sim_scores=np.zeros((1, 1), np.float32),
        sim_idx=np.zeros((1, 1), np.int64),
        item_vocab=vocab,
        serve_dtype="bf16",
    )
    m2 = pickle.loads(pickle.dumps(m))
    assert m2.serve_dtype == "bf16"
    # pre-ISSUE-14 state without the field defaults to f32
    legacy = ItemSimModel.__new__(ItemSimModel)
    legacy.__setstate__({
        "sim_scores": np.zeros((1, 1), np.float32),
        "sim_idx": np.zeros((1, 1), np.int64),
        "item_vocab": vocab,
    })
    assert legacy.serve_dtype == "f32" and legacy.top_n == 50


# ---------------------------------------------------------------------------
# devprof: per-dtype columns for mixed-dtype executables
# ---------------------------------------------------------------------------


def test_devprof_mixed_dtype_executable_reports_both_columns(monkeypatch):
    from predictionio_tpu.obs import devprof

    monkeypatch.setenv("PIO_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("PIO_PEAK_FLOPS_INT8", "4e12")
    monkeypatch.setenv("PIO_PEAK_FLOPS_F32", "5e11")
    prof = devprof.DeviceProfiler()
    monkeypatch.setattr(devprof, "_profiler", prof)

    calls = {"dt": "f32"}
    fn = jax.jit(lambda a, b: a @ b)
    wrapped = devprof.instrument(
        "test.mixed_mm", fn, dtype_of=lambda a, k: calls["dt"]
    )
    x32 = jnp.asarray(
        np.random.RandomState(0).standard_normal((64, 64)), jnp.float32
    )
    np.asarray(wrapped(x32, x32))
    calls["dt"] = "int8"
    x16 = jnp.asarray(
        np.random.RandomState(0).standard_normal((128, 128)),
        jnp.float32,
    )
    np.asarray(wrapped(x16, x16))
    rep = prof.executable("test.mixed_mm")
    assert rep is not None
    cols = rep.get("dtypes")
    assert cols is not None and set(cols) == {"f32", "int8"}
    assert cols["f32"]["peak_flops"] == 5e11
    assert cols["int8"]["peak_flops"] == 4e12
    assert cols["f32"]["invocations"] == 1
    assert cols["int8"]["invocations"] == 1
    # the legacy scalar fields still reflect the LATEST signature
    assert rep["dtype"] == "int8"


def test_serving_similar_reports_dtype():
    from predictionio_tpu.obs import devprof

    rng = np.random.RandomState(41)
    f = _factors(rng, u=16, i=200, k=8)
    sv = als.stage_serving(f, serve_dtype="int8")
    als.similar_serving(sv, np.arange(4), 5)
    rep = devprof.get_profiler().executable("als.similar_serving")
    assert rep is not None and rep.get("dtype") in ("int8", "f32", "bf16")


def test_xla_scores_batch_size_invariant():
    """The shadow-rollout agreement contract: a B=1 mirror and a B=n
    live batch of the SAME query must produce bit-identical scores on
    the XLA fallback (the transposed-contraction dot_general this PR
    briefly used rounded differently per batch size — regression)."""
    rng = np.random.RandomState(50)
    f = _factors(rng, u=16, i=40, k=8)
    for dt in ("f32", "bf16", "int8"):
        sv = dataclasses.replace(
            als.stage_serving(f, serve_dtype=dt), mode=None
        )
        single = als.recommend_serving(sv, [3], 7)
        batched = als.recommend_serving(sv, [0, 3, 5, 7], 7)
        assert np.array_equal(single[1][0], batched[1][1]), dt
        assert np.array_equal(single[0][0], batched[0][1]), dt
        s1 = als.similar_serving(sv, [3], 7)
        s4 = als.similar_serving(sv, [0, 3, 5, 7], 7)
        assert np.array_equal(s1[1][0], s4[1][1]), dt
        assert np.array_equal(s1[0][0], s4[0][1]), dt


@needs_mesh
def test_sharded_within_pad_growth_becomes_servable():
    """Within-pad item growth through the fold-in carry must raise the
    LIVE extent — without it the grown rows stay masked dead under the
    verbs' live-count gates while the single-device tier serves them
    (review regression)."""
    from predictionio_tpu.fleet.runtime import ShardedRuntime

    rng = np.random.RandomState(51)
    uf = rng.standard_normal((16, 8)).astype(np.float32)
    itf = rng.standard_normal((100, 8)).astype(np.float32)
    srt = ShardedRuntime(uf, itf, serve_dtype="int8")
    i_p = int(srt._state.itf.shape[0])
    assert i_p > 102  # pad headroom exists
    boost = np.full((2, 8), 9.0, np.float32)
    srt.update_item_rows(np.array([100, 101]), boost, n_items=102)
    assert srt.n_items == 102
    srt.update_user_rows(
        np.array([0]), np.full((1, 8), 1.0, np.float32), n_users=16
    )
    _, idx = srt.recommend([0], 2)
    assert set(np.asarray(idx[0])) == {100, 101}
