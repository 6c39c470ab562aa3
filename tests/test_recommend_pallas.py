"""Fused serving-kernel parity + int8 serving state + donated publish
(ISSUE 11).

The fused Pallas recommend+top-k kernel must agree EXACTLY with the
XLA two-step reference (`ops.topk.masked_top_k` over `q @ itf.T`) —
values, indices, and tie order — in interpret mode on CPU; int8
serving must agree with its own plain-XLA int8 reference exactly and
with f32 scoring within the quantization bound; and the fold-in
publish path must be copy-on-write: a runtime swap mid-flight leaves
every reader of the OLD staged state with correct, unchanged answers
(donation only ever touches buffers the publish privately created)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from predictionio_tpu.data.store.bimap import BiMap  # noqa: E402
from predictionio_tpu.models import als  # noqa: E402
from predictionio_tpu.ops.recommend_pallas import (  # noqa: E402
    ITEM_PAD,
    ITEM_TILES,
    PAD_WASTE,
    fused_recommend_topk,
    pad_items,
    pick_item_tile,
    quantize_rows_jnp,
    quantize_rows_np,
)
from predictionio_tpu.ops.topk import NEG_INF, masked_top_k  # noqa: E402


def _pad(itf, i_p):
    out = np.zeros((i_p, itf.shape[1]), itf.dtype)
    out[: itf.shape[0]] = itf
    return out


def _fused(uf, itf, k, mask=None):
    from predictionio_tpu.ops.recommend_pallas import pack_mask_np

    i_p = pad_items(itf.shape[0])
    bits = None
    if mask is not None:
        # exclusion ships bit-packed (ISSUE 14): 1/32 the f32 bytes
        bits = jnp.asarray(pack_mask_np(mask, i_p))
    return fused_recommend_topk(
        jnp.asarray(uf), jnp.asarray(_pad(itf, i_p)), None, None, bits,
        k=k, n_items=itf.shape[0], interpret=True,
    )


# ---------------------------------------------------------------------------
# kernel parity (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 5, 128, 300])
def test_fused_parity_unmasked(k):
    rng = np.random.RandomState(0)
    uf = rng.standard_normal((8, 10)).astype(np.float32)
    itf = rng.standard_normal((300, 10)).astype(np.float32)
    ref_v, ref_i = masked_top_k(jnp.asarray(uf @ itf.T), k, None)
    v, ix = _fused(uf, itf, k)
    assert np.array_equal(np.asarray(ref_i), np.asarray(ix))
    np.testing.assert_allclose(
        np.asarray(ref_v), np.asarray(v), rtol=1e-6
    )


def test_fused_parity_masked():
    rng = np.random.RandomState(1)
    uf = rng.standard_normal((8, 10)).astype(np.float32)
    itf = rng.standard_normal((300, 10)).astype(np.float32)
    mask = rng.rand(8, 300) < 0.4
    ref_v, ref_i = masked_top_k(
        jnp.asarray(uf @ itf.T), 17, jnp.asarray(mask)
    )
    v, ix = _fused(uf, itf, 17, mask=mask)
    assert np.array_equal(np.asarray(ref_i), np.asarray(ix))
    np.testing.assert_allclose(
        np.asarray(ref_v), np.asarray(v), rtol=1e-6
    )


def test_fused_fully_masked_row_matches_reference():
    """A row whose every item is excluded must return NEG_INF values at
    the reference's tie order (indices 0..k-1)."""
    rng = np.random.RandomState(2)
    uf = rng.standard_normal((2, 4)).astype(np.float32)
    itf = rng.standard_normal((200, 4)).astype(np.float32)
    mask = np.zeros((2, 200), bool)
    mask[1, :] = True
    ref_v, ref_i = masked_top_k(
        jnp.asarray(uf @ itf.T), 6, jnp.asarray(mask)
    )
    v, ix = _fused(uf, itf, 6, mask=mask)
    assert np.array_equal(np.asarray(ref_i), np.asarray(ix))
    assert np.all(np.asarray(v)[1] == NEG_INF)


def test_fused_tie_breaking_matches_lax_top_k():
    """Equal scores everywhere — the stable (lowest index first) order
    must match lax.top_k bit-for-bit, including across tile
    boundaries."""
    uf = np.ones((2, 4), np.float32)
    itf = np.tile(np.array([[1, 0, 0, 0]], np.float32), (260, 1))
    ref_v, ref_i = masked_top_k(jnp.asarray(uf @ itf.T), 140, None)
    v, ix = _fused(uf, itf, 140)
    assert np.array_equal(np.asarray(ref_i), np.asarray(ix))

    # duplicated score blocks straddling the 128-row tile boundary
    rng = np.random.RandomState(3)
    base = rng.standard_normal((130, 6)).astype(np.float32)
    itf2 = np.concatenate([base, base])  # every score appears twice
    uf2 = rng.standard_normal((3, 6)).astype(np.float32)
    ref_v, ref_i = masked_top_k(jnp.asarray(uf2 @ itf2.T), 50, None)
    v2, ix2 = _fused(uf2, itf2, 50)
    assert np.array_equal(np.asarray(ref_i), np.asarray(ix2))


def test_fused_small_catalog_k_equals_n():
    rng = np.random.RandomState(4)
    uf = rng.standard_normal((1, 8)).astype(np.float32)
    itf = rng.standard_normal((7, 8)).astype(np.float32)
    ref_v, ref_i = masked_top_k(jnp.asarray(uf @ itf.T), 7, None)
    v, ix = _fused(uf, itf, 7)
    assert np.array_equal(np.asarray(ref_i), np.asarray(ix))


def test_pick_item_tile_always_divides():
    for n in (128, 256, 384, 26744 + 72, 1024, 2048, 131072):
        n_p = pad_items(n)
        t = pick_item_tile(n_p)
        assert t > 0 and n_p % t == 0


@pytest.mark.parametrize("n_items, shards, fused, padded, tile", [
    (5_700_000, 1, True, 5_701_632, 2048),  # serve-steady's catalogue
    (19_700_000, 4, True, 19_701_760, 2048),  # serve-sharded's: 4,925,440 a shard
    (19_700_000, 4, False, 19_700_096, None),  # XLA path: 32 a shard
    (0, 1, True, 128, 128),
    (0, 8, False, 256, None),
    # small: the next multiple of 128 a shard, as before the rule knew sizes
    (300, 1, True, 384, 128),
    (1_321, 4, True, 1_536, 128),  # 384 a shard: alive on every shard
    (8_000, 1, True, 8_064, 128),
    # in between the tile rises with the size
    (26_744, 1, True, 27_136, 512),  # ML-20M's catalogue
    (26_744, 4, True, 27_136, 128),  # 6,784 a shard
    (100_000, 1, True, 100_352, 2048),  # the UR catalogue
    (131_073, 1, True, 133_120, 2048),  # from here every size takes 2,048
])
def test_pad_items_is_the_pad_rule_of_both_tiers(
        n_items, shards, fused, padded, tile):
    """One function beside the tile ladder says how many item rows either
    tier stages (`als._stage_arrays`, `ShardedRuntime.__init__`): every
    shard's slab a multiple of the widest tile whose pad is at most
    PAD_WASTE of its rows where a fused mode resolved, of 32 (whole
    packed-mask words) on the sharded XLA path."""
    got = pad_items(n_items, shards, fused=fused)
    assert got == padded and got % shards == 0
    slab = got // shards
    assert slab % 32 == 0
    if fused:
        assert pick_item_tile(slab) == tile


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_pad_items_holds_its_bounds_over_a_sweep_of_sizes(shards):
    """A tile divides every slab; a slab is whole packed-mask words; the
    pad is under ITEM_PAD rows a shard or within PAD_WASTE of the slab's
    rows; the count never falls below the old rule's (the next multiple
    of shards x ITEM_PAD) nor as the catalogue grows; and a shard is
    never all pad where the old rule left it live rows."""
    rng = np.random.RandomState(shards)
    sizes = np.unique(np.concatenate([
        np.arange(0, 600), 2 ** np.arange(7, 25), 2 ** np.arange(7, 25) + 1,
        (10 ** rng.uniform(2, 7.5, 2000)).astype(np.int64),
    ]))
    before = 0
    for n in map(int, sizes):
        got = pad_items(n, shards)
        slab, rows = got // shards, -(-max(n, 1) // shards)
        old = -(-max(n, 1) // (shards * ITEM_PAD)) * shards * ITEM_PAD
        assert got % shards == 0 and slab % 32 == 0
        assert pick_item_tile(slab) in ITEM_TILES
        assert slab - rows < ITEM_PAD or slab - rows <= rows * PAD_WASTE
        assert got >= old and got >= before
        # the last shard keeps live rows wherever the old rule's did
        if n > old - old // shards:
            assert n > got - slab
        before = got


# ---------------------------------------------------------------------------
# int8 quantized serving
# ---------------------------------------------------------------------------


def test_int8_kernel_matches_xla_int8_reference_exactly():
    rng = np.random.RandomState(5)
    uf = rng.standard_normal((8, 10)).astype(np.float32)
    itf = rng.standard_normal((300, 10)).astype(np.float32)
    q8, qs = quantize_rows_np(uf)
    i8, isc = quantize_rows_np(itf)
    i_p = pad_items(300)
    i8_p = np.zeros((i_p, 10), np.int8)
    i8_p[:300] = i8
    isc_p = np.ones((1, i_p), np.float32)
    isc_p[0, :300] = isc
    v, ix = fused_recommend_topk(
        jnp.asarray(q8), jnp.asarray(i8_p), jnp.asarray(qs[:, None]),
        jnp.asarray(isc_p), k=10, n_items=300, interpret=True,
    )
    s_ref = (
        q8.astype(np.int32) @ i8.T.astype(np.int32)
    ).astype(np.float32) * qs[:, None] * isc[None, :]
    rv, ri = masked_top_k(jnp.asarray(s_ref), 10, None)
    assert np.array_equal(np.asarray(ri), np.asarray(ix))
    np.testing.assert_allclose(np.asarray(rv), np.asarray(v), rtol=1e-5)


def test_int8_round_trip_score_agreement_bound():
    """Per-row symmetric int8 quantization of BOTH sides: the score
    error is bounded by ~2/127 per side of the max-magnitude product —
    assert a 2.5% relative bound on this workload and that dequantized
    factors round-trip within one quantization step."""
    rng = np.random.RandomState(6)
    uf = rng.standard_normal((64, 10)).astype(np.float32)
    itf = rng.standard_normal((500, 10)).astype(np.float32)
    q8, qs = quantize_rows_np(uf)
    i8, isc = quantize_rows_np(itf)
    # round trip: |deq - orig| <= scale/2 per element
    deq = q8.astype(np.float32) * qs[:, None]
    assert np.all(np.abs(deq - uf) <= qs[:, None] / 2 + 1e-7)
    s_f32 = uf @ itf.T
    s_int8 = (
        q8.astype(np.int32) @ i8.T.astype(np.int32)
    ).astype(np.float32) * qs[:, None] * isc[None, :]
    denom = np.abs(s_f32).max()
    assert np.max(np.abs(s_int8 - s_f32)) / denom < 0.025
    # traced quantizer agrees with the host one
    qj, sj = quantize_rows_jnp(jnp.asarray(uf))
    assert np.array_equal(np.asarray(qj), q8)
    np.testing.assert_allclose(np.asarray(sj)[:, 0], qs, rtol=1e-6)


def _factors(rng, u=50, i=300, k=10):
    return als.ALSFactors(
        user_factors=rng.standard_normal((u, k)).astype(np.float32),
        item_factors=rng.standard_normal((i, k)).astype(np.float32),
        user_vocab=BiMap({f"u{n}": n for n in range(u)}),
        item_vocab=BiMap({f"i{n}": n for n in range(i)}),
    )


@pytest.mark.parametrize("dtype", ["f32", "int8"])
@pytest.mark.parametrize("mode", [None, "interpret"])
def test_recommend_serving_parity(dtype, mode):
    """The staged-state path must match the legacy recommend exactly at
    f32 (either kernel mode), and at int8 match its own int8 scoring
    across modes — a mode change never changes scores."""
    import dataclasses

    rng = np.random.RandomState(7)
    f = _factors(rng)
    ref_v, ref_i = als.recommend(f, np.arange(8), 10)
    sv = dataclasses.replace(
        als.stage_serving(f, serve_dtype=dtype), mode=mode
    )
    v, ix = als.recommend_serving(sv, np.arange(8), 10)
    if dtype == "f32":
        assert np.array_equal(ix, ref_i)
        np.testing.assert_allclose(v, ref_v, rtol=1e-5)
    else:
        # int8 vs the XLA int8 path (mode=None) must be identical
        sv0 = dataclasses.replace(sv, mode=None)
        v0, ix0 = als.recommend_serving(sv0, np.arange(8), 10)
        assert np.array_equal(ix, ix0)
        np.testing.assert_allclose(v, v0, rtol=1e-5)
    # masked never returns an excluded item
    mask = rng.rand(8, 300) < 0.5
    v2, ix2 = als.recommend_serving(
        sv, np.arange(8), 10, exclude_mask=mask
    )
    assert not np.any(mask[np.arange(8)[:, None], ix2])


# ---------------------------------------------------------------------------
# donated publish + swap safety
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_serving_publish_rows_is_copy_on_write(dtype):
    rng = np.random.RandomState(8)
    f = _factors(rng)
    sv = als.stage_serving(f, serve_dtype=dtype)
    before_v, before_i = als.recommend_serving(sv, [0, 1], 10)
    new_rows = rng.standard_normal((2, 10)).astype(np.float32)
    sv2 = als.serving_publish_rows(
        sv, user_rows=[0, 1], user_vals=new_rows
    )
    # the OLD state still serves the OLD answers (readers are safe)
    again_v, again_i = als.recommend_serving(sv, [0, 1], 10)
    assert np.array_equal(before_i, again_i)
    np.testing.assert_allclose(before_v, again_v, rtol=1e-7)
    # the successor serves the published rows
    v2, _ = als.recommend_serving(sv2, [0, 1], 10)
    assert not np.allclose(before_v, v2)


@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_serving_publish_growth_donates_only_private_buffers(dtype):
    """Vocab growth uses the donated fast path — and the old state's
    buffers must remain alive and correct (donation only applies to the
    freshly-grown private successor)."""
    rng = np.random.RandomState(9)
    f = _factors(rng)
    sv = als.stage_serving(f, serve_dtype=dtype)
    old_v, old_i = als.recommend_serving(sv, [3], 10)
    # grow users beyond the staged extent and items beyond the pad
    i_p = int(sv.items.shape[0])
    sv2 = als.serving_publish_rows(
        sv,
        user_rows=[50, 51], user_vals=np.ones((2, 10), np.float32),
        item_rows=[i_p, i_p + 1],
        item_vals=rng.standard_normal((2, 10)).astype(np.float32),
        n_users=52, n_items=i_p + 2,
    )
    assert sv2.n_users == 52 and sv2.n_items == i_p + 2
    # grown users are servable; old state unchanged (mid-flight reader)
    gv, gi = als.recommend_serving(sv2, [50], 10)
    assert gv.shape == (1, 10)
    again_v, again_i = als.recommend_serving(sv, [3], 10)
    assert np.array_equal(old_i, again_i)
    np.testing.assert_allclose(old_v, again_v, rtol=1e-7)


def test_vocab_growth_within_pad_does_not_retrace_serving():
    """n_items rides the serving jit as a TRACED scalar: an online fold
    tick that grows the item vocab within the pad headroom must reuse
    the compiled serving program (a retrace per growth tick would dwarf
    the row-publish saving the COW path exists for)."""
    rng = np.random.RandomState(42)
    f = _factors(rng, u=20, i=100, k=8)
    sv = als.stage_serving(f, serve_dtype="int8")
    als.recommend_serving(sv, [0, 1], 5)
    inner = als._serve_recommend_jit.__wrapped__
    n0 = inner._cache_size()
    sv2 = als.serving_publish_rows(
        sv, item_rows=[100, 101, 102],
        item_vals=rng.standard_normal((3, 8)).astype(np.float32),
        n_items=103,
    )
    v, ix = als.recommend_serving(sv2, [0, 1], 5)
    assert inner._cache_size() == n0
    assert ix.max() <= 102  # the grown rows are really servable


def test_fold_in_clone_carries_serving_state_via_row_publish():
    """online/foldin.py:_clone_model threads dirty rows into
    ResidentServing.adopt: the clone's staged state reflects the fold
    WITHOUT a restage, keeps the serve dtype, and drops the carry when
    a changed side has no row attribution."""
    from predictionio_tpu.engines.recommendation.engine import ALSModel
    from predictionio_tpu.online.foldin import ALSFoldIn

    rng = np.random.RandomState(10)
    f = _factors(rng)
    model = ALSModel(f, serve_dtype="int8")
    sv = model.resident.get()
    assert sv.dtype == "int8"
    new_uf = f.user_factors.copy()
    solved = rng.standard_normal((2, 10)).astype(np.float32)
    new_uf[[1, 2]] = solved
    import dataclasses

    nf = dataclasses.replace(f, user_factors=new_uf)
    clone = ALSFoldIn._clone_model(
        model, nf, items_changed=False,
        dirty_users=([1, 2], solved),
    )
    assert clone.serve_dtype == "int8"
    assert clone.resident.device_bytes() is not None  # carried, staged
    # the clone's staged state serves the folded rows (quantized)
    v_new, _ = als.recommend_serving(clone.resident.get(), [1], 5)
    v_model = als.recommend_serving(
        als.stage_serving(nf, serve_dtype="int8"), [1], 5
    )[0]
    np.testing.assert_allclose(v_new, v_model, rtol=1e-5)
    # no row attribution for a changed side -> carry dropped
    clone2 = ALSFoldIn._clone_model(
        model, nf, items_changed=False
    )
    assert clone2.resident.device_bytes() is None


# ---------------------------------------------------------------------------
# sharded twin (forced multi-device CPU mesh, interpret mode)
# ---------------------------------------------------------------------------


def test_sharded_fused_recommend_parity(monkeypatch):
    if len(jax.devices()) < 2:
        pytest.skip("needs the forced multi-device CPU mesh")
    from predictionio_tpu.fleet.runtime import ShardedRuntime

    rng = np.random.RandomState(11)
    uf = rng.standard_normal((40, 8)).astype(np.float32)
    itf = rng.standard_normal((570, 8)).astype(np.float32)
    fused = ShardedRuntime(uf, itf, serve_mode="interpret")
    plain = ShardedRuntime(uf, itf, serve_mode="off")
    assert fused.serve_mode == "interpret" and plain.serve_mode is None
    v, ix = fused.recommend(np.arange(6), 10)
    v2, ix2 = plain.recommend(np.arange(6), 10)
    assert np.array_equal(ix, ix2)
    np.testing.assert_allclose(v, v2, rtol=1e-5)
    mask = rng.rand(6, 570) < 0.4
    v, ix = fused.recommend(np.arange(6), 10, exclude_mask=mask)
    v2, ix2 = plain.recommend(np.arange(6), 10, exclude_mask=mask)
    assert np.array_equal(ix, ix2)


def test_sharded_similar_vectors_ranking():
    if len(jax.devices()) < 2:
        pytest.skip("needs the forced multi-device CPU mesh")
    from predictionio_tpu.fleet.runtime import ShardedRuntime

    rng = np.random.RandomState(12)
    itf = rng.standard_normal((300, 8)).astype(np.float32)
    srt = ShardedRuntime(np.zeros((0, 8), np.float32), itf)
    vecs = rng.standard_normal((3, 8)).astype(np.float32)
    vals, idx = srt.similar_vectors(vecs, 7)
    qn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    fn = itf / np.linalg.norm(itf, axis=1, keepdims=True)
    ref = np.argsort(-(qn @ fn.T), axis=1, kind="stable")[:, :7]
    assert np.array_equal(idx, ref)
    # exclusion mask respected
    mask = np.zeros((3, 300), bool)
    mask[:, ref[:, 0]] = True
    _, idx2 = srt.similar_vectors(vecs, 7, exclude_mask=mask)
    for r in range(3):
        assert ref[r, 0] not in idx2[r]


# ---------------------------------------------------------------------------
# devprof dtype-aware roofline (ISSUE 11 satellite)
# ---------------------------------------------------------------------------


def test_devprof_dtype_peaks(monkeypatch):
    from predictionio_tpu.obs import devprof

    monkeypatch.setenv("PIO_PEAK_FLOPS", "100e12")
    assert devprof.platform_info()["peak_flops"] == 100e12
    # the central override pins every dtype unless a dtyped env is set
    assert devprof.platform_info("int8")["peak_flops"] == 100e12
    monkeypatch.setenv("PIO_PEAK_FLOPS_INT8", "200e12")
    assert devprof.platform_info("int8")["peak_flops"] == 200e12
    assert devprof.platform_info("f32")["peak_flops"] == 100e12
    monkeypatch.setenv("PIO_PEAK_FLOPS_F32", "50e12")
    assert devprof.platform_info("f32")["peak_flops"] == 50e12
    # dtyped mfu uses the dtyped peak
    assert devprof.mfu(1e12, 1.0, "int8") == pytest.approx(1 / 200)
    assert devprof.mfu(1e12, 1.0, "f32") == pytest.approx(1 / 50)


def test_devprof_executable_reports_dtype(monkeypatch):
    """An instrumented executable with a dtype_of hook rooflines its
    MFU against that dtype's peak and surfaces `dtype` in the report."""
    from predictionio_tpu.obs import devprof

    monkeypatch.setenv("PIO_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("PIO_PEAK_FLOPS_INT8", "4e12")
    prof = devprof.DeviceProfiler()
    monkeypatch.setattr(devprof, "_profiler", prof)

    fn = jax.jit(lambda a, b: (a @ b))
    wrapped = devprof.instrument(
        "test.int8_mm", fn, dtype_of=lambda a, k: "int8"
    )
    x = jnp.asarray(
        np.random.RandomState(0).randint(-3, 3, (64, 64)), jnp.int8
    )
    np.asarray(wrapped(x.astype(jnp.float32), x.astype(jnp.float32).T))
    rep = prof.executable("test.int8_mm")
    assert rep is not None
    assert rep.get("dtype") == "int8"
    if rep.get("mfu") is not None:
        # the dtyped denominator was used
        assert rep["peak_flops_dtype"] == 4e12


def test_serving_jit_reports_int8_dtype():
    """End to end: an int8 staged-serving call lands in devprof with
    dtype int8 on the als.recommend_serving executable."""
    from predictionio_tpu.obs import devprof

    rng = np.random.RandomState(13)
    f = _factors(rng, u=16, i=200)
    sv = als.stage_serving(f, serve_dtype="int8")
    als.recommend_serving(sv, np.arange(4), 5)
    rep = devprof.get_profiler().executable("als.recommend_serving")
    assert rep is not None and rep.get("dtype") in ("int8", "f32")
