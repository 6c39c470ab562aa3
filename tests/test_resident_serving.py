"""`models/resident.py` `ResidentServing`: the one owner of a model's
device-resident serving state for the recommendation, similarproduct and
itemsim engines — which tier serves, staging, the fold-in carry, what it
reports, how it pickles and how it is released."""

from __future__ import annotations

import dataclasses
import gc
import pickle
import time
import weakref

import jax
import numpy as np
import pytest

from predictionio_tpu.data.store.bimap import BiMap
from predictionio_tpu.engines.itemsim import engine as itemsim
from predictionio_tpu.engines.recommendation import engine as reco
from predictionio_tpu.engines.similarproduct import engine as simprod
from predictionio_tpu.fleet.runtime import OversizedModelError, ShardedRuntime
from predictionio_tpu.models import als
from predictionio_tpu.models.resident import ResidentServing
from predictionio_tpu.online.foldin import ALSFoldIn

N_USERS, N_ITEMS, RANK = 40, 300, 8


def _factors(seed=0, u=N_USERS, i=N_ITEMS, k=RANK):
    rng = np.random.RandomState(seed)
    return als.ALSFactors(
        user_factors=rng.standard_normal((u, k)).astype(np.float32),
        item_factors=rng.standard_normal((i, k)).astype(np.float32),
        user_vocab=BiMap({f"u{n}": n for n in range(u)}),
        item_vocab=BiMap({f"i{n}": n for n in range(i)}),
    )


def _itemsim_model(serve_dtype="f32", n_items=12, n_users=20):
    rng = np.random.RandomState(3)
    vectors = (rng.rand(n_items, n_users) < 0.4).astype(np.float32)
    vectors[:, 0] = 1.0  # no all-zero column vector
    empty = np.zeros((0, 0), np.float32)
    return itemsim.ItemSimModel(
        sim_scores=empty, sim_idx=empty.astype(np.int64),
        item_vocab=BiMap({f"i{n}": n for n in range(n_items)}),
        top_n=5, item_vectors=vectors, serve_dtype=serve_dtype,
    )


def _served(engine: str):
    """(model, a function that answers one fixed query through the
    engine's own predict path on the resident state)."""
    if engine == "recommendation":
        model = reco.ALSModel(_factors(), serve_dtype="int8")
        algo = reco.ALSAlgorithm(reco.ALSAlgorithmParams(serve_dtype="int8"))
        query = reco.Query(user="u3", num=5, blacklist=["i1"])
    elif engine == "similarproduct":
        # int8 takes the staged verb on a CPU too (f32 keeps the host path)
        model = simprod.SimilarModel(_factors(), serve_dtype="int8")
        algo = simprod.ALSSimilarAlgorithm(
            simprod.ALSSimilarParams(serve_dtype="int8")
        )
        query = simprod.Query(items=["i2", "i7"], num=5)
    else:
        model = _itemsim_model()
        algo = itemsim.ItemSimAlgorithm(itemsim.ItemSimAlgorithmParams())
        query = itemsim.Query(items=["i2"], num=4)

    def answer(m):
        return [
            (s.item, round(s.score, 5))
            for s in algo.predict(m, query).item_scores
        ]

    return model, answer


ENGINES = ("recommendation", "similarproduct", "itemsim")


@pytest.fixture
def mesh_devices():
    if len(jax.devices()) < 2:
        pytest.skip("needs the forced multi-device host mesh")
    return len(jax.devices())


# -- pickling ---------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_a_model_pickles_with_nothing_staged_and_serves_the_same(engine):
    model, answer = _served(engine)
    before = answer(model)
    assert before and model.resident.device_bytes() is not None  # staged
    clone = pickle.loads(pickle.dumps(model))
    assert clone.resident.device_bytes() is None  # nothing staged rode along
    assert clone.resident.info() is None
    assert answer(clone) == before
    assert clone.resident.device_bytes() == model.resident.device_bytes()


def test_an_itemsim_state_pickled_before_the_object_existed_still_serves():
    model, answer = _served("itemsim")
    before = answer(model)
    state = {k: v for k, v in model.__dict__.items() if k != "resident"}
    legacy = itemsim.ItemSimModel.__new__(itemsim.ItemSimModel)
    legacy.__setstate__(state)
    assert legacy.resident.device_bytes() is None
    assert answer(legacy) == before


def test_resident_serving_pickles_as_its_constructor_arguments():
    resident = ResidentServing(_factors(), "int8", item_only=True)
    resident.get()
    clone = pickle.loads(pickle.dumps(resident))
    assert (clone.serve_dtype, clone.item_only) == ("int8", True)
    assert clone.device_bytes() is None
    np.testing.assert_array_equal(
        clone.factors.item_factors, resident.factors.item_factors
    )


# -- which tier -------------------------------------------------------------


def test_shard_on_one_visible_device_serves_one_chip_and_probes_once(
        monkeypatch):
    one = jax.devices()[:1]
    probes = []

    def devices(*args):
        probes.append(args)
        return one

    monkeypatch.setattr(jax, "devices", devices)
    resident = ResidentServing(_factors())
    state = resident.get(shard=True)
    assert isinstance(state, als.ServingFactors)
    assert not resident.is_sharded(True)
    # the fleet status sees no sharded tier; the layout is the one chip's
    assert resident.sharded_info() is None and resident.info()["shards"] == 1
    asked = len(probes)
    assert asked >= 1
    v, ix = resident.recommend([1, 2], 5, shard=True)
    assert resident.get(shard=True) is state
    assert len(probes) == asked  # the outcome is kept, not asked again
    ref = als.recommend_serving(als.stage_serving(_factors()), [1, 2], 5)
    np.testing.assert_array_equal(ix, ref[1])


@pytest.mark.parametrize("shard", [False, True])
def test_info_and_the_staging_span_name_the_rows_staged_and_their_tile(
        shard, mesh_devices):
    """What `pad_items` decided from the catalogue's size is on the
    staging span and in `info()` on either tier (ISSUE 31): 300 rows pad
    to 384 on one chip, tile 128 at this size (2,048 from 131 k rows a
    shard); on the mesh no kernel mode resolves on a CPU, so the 38 rows
    a shard pad to the XLA path's 64 and no tile is named."""
    from predictionio_tpu.obs import spans

    t0 = time.time()
    resident = ResidentServing(_factors())
    assert resident.info() is None  # nothing staged
    resident.get(shard=shard)
    shards = mesh_devices if shard else 1
    info = resident.info()
    assert info["shards"] == shards and info["n_items"] == N_ITEMS
    assert info["item_rows_padded"] == (shards * 64 if shard else 384)
    assert info["item_tile"] == (0 if shard else 128)
    assert (resident.sharded_info() is not None) == shard
    name = "sharded.stage" if shard else "als.serve.stage"
    (span,) = [s for s in spans.get_default_recorder().recent(t0)
               if s.name == name]
    assert span.attrs["item_rows_padded"] == info["item_rows_padded"]
    assert span.attrs["item_tile"] == info["item_tile"]


def test_shard_on_a_mesh_stages_the_sharded_tier_only(mesh_devices):
    resident = ResidentServing(_factors())
    assert resident.is_sharded(True)
    srt = resident.get(shard=True)
    assert isinstance(srt, ShardedRuntime)
    assert resident.info()["shards"] == mesh_devices
    assert resident.device_bytes() == srt.device_bytes()["per_shard"]
    # shard off on the same object: the one-chip tier, staged beside it
    assert isinstance(resident.get(), als.ServingFactors)
    assert resident.get(shard=True) is srt


# -- the three verbs against direct calls -----------------------------------


@pytest.mark.parametrize("dtype", ["f32", "int8"])
@pytest.mark.parametrize("shard", [False, True], ids=["one-chip", "sharded"])
def test_the_three_verbs_equal_direct_calls(dtype, shard, request):
    if shard:
        request.getfixturevalue("mesh_devices")
    f = _factors(seed=7)
    resident = ResidentServing(f, dtype)
    rows = np.array([0, 5, 9], np.int64)
    mask = np.zeros((3, N_ITEMS), bool)
    mask[:, ::3] = True
    ex_rows = np.array([[4, -1], [8, 2], [-1, -1]], np.int32)
    vecs = f.item_factors[[3, 11]]
    if shard:
        direct = ShardedRuntime.from_factors(f, serve_dtype=dtype)
        want = [
            direct.recommend(rows, 10, exclude_rows=ex_rows),
            direct.recommend(rows, 10, exclude_mask=mask),
            direct.similar_items(rows, 10, exclude_self=True),
            direct.similar_vectors(vecs, 10, exclude_mask=mask[:2]),
        ]
    else:
        direct = als.stage_serving(f, serve_dtype=dtype)
        want = [
            als.recommend_serving(direct, rows, 10, exclude_rows=ex_rows),
            als.recommend_serving(direct, rows, 10, exclude_mask=mask),
            als.similar_serving(direct, rows, 10, exclude_self=True),
            als.similar_vectors_serving(
                direct, vecs, 10, exclude_mask=mask[:2]
            ),
        ]
    got = [
        resident.recommend(rows, 10, exclude_rows=ex_rows, shard=shard),
        resident.recommend(rows, 10, exclude_mask=mask, shard=shard),
        resident.similar_items(rows, 10, exclude_self=True, shard=shard),
        resident.similar_vectors(
            vecs, 10, exclude_mask=mask[:2], shard=shard
        ),
    ]
    for (gv, gi), (wv, wi) in zip(got, want):
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)


# -- the fold-in carry ------------------------------------------------------


@pytest.mark.parametrize("shard", [False, True], ids=["one-chip", "sharded"])
def test_adopt_carries_a_dirty_row_and_drops_what_it_cannot_express(
        shard, request):
    if shard:
        request.getfixturevalue("mesh_devices")
    f = _factors(seed=11)
    old = ResidentServing(f, "int8")
    state = old.get(shard)
    solved = np.random.RandomState(1).standard_normal((2, RANK)).astype(
        np.float32
    )
    new_uf = f.user_factors.copy()
    new_uf[[1, 2]] = solved
    nf = dataclasses.replace(f, user_factors=new_uf)

    new = ResidentServing(nf, "int8")
    new.adopt(old, dirty_users=([1, 2], solved))
    assert new.device_bytes() is not None  # carried: nothing to restage
    if shard:
        assert new.get(shard) is state  # the resident slabs, in place
    got = new.recommend([1, 2], 5, shard=shard)
    fresh = ResidentServing(nf, "int8").recommend([1, 2], 5, shard=shard)
    np.testing.assert_array_equal(got[1], fresh[1])
    np.testing.assert_allclose(got[0], fresh[0], rtol=1e-5)

    # what row writes cannot express drops the carry: the next query
    # restages from the folded factors
    grown = dataclasses.replace(
        nf, item_factors=np.concatenate(
            [nf.item_factors, np.ones((4000, RANK), np.float32)]
        ),
    )
    dropped = ResidentServing(grown, "int8")
    if shard:
        # an item row past the padded shard extent
        past = int(state.info()["n_items"]) + 3999
        dropped.adopt(new, dirty_items=(
            [past], np.ones((1, RANK), np.float32)))
        assert state.n_items == N_ITEMS  # validated before any write
    else:
        # a side that grew without row attribution
        dropped.adopt(new)
    assert dropped.device_bytes() is None and dropped.info() is None
    assert dropped.recommend([1], 5, shard=shard)[1].shape == (1, 5)


@pytest.mark.parametrize("engine", ["recommendation", "similarproduct",
                                    "ecommerce"])
def test_a_fold_clone_keeps_its_class_dtype_and_categories(engine):
    f = _factors()
    grown = dataclasses.replace(
        f, item_factors=np.concatenate(
            [f.item_factors, np.zeros((2, RANK), np.float32)]),
    )
    cats = [frozenset({"c"})] * N_ITEMS
    if engine == "recommendation":
        model = reco.ALSModel(f, item_categories=cats, serve_dtype="int8")
    elif engine == "similarproduct":
        model = simprod.SimilarModel(f, serve_dtype="int8")
    else:
        from predictionio_tpu.engines.ecommerce.engine import ECommModel

        model = ECommModel(f, cats)
    assert ALSFoldIn.find_model(
        dataclasses.make_dataclass("R", ["models"])([model])
    ) == (0, model)
    clone = ALSFoldIn._clone_model(
        model, grown, items_changed=True, users_changed=False,
        dirty_items=([N_ITEMS], np.zeros((1, RANK), np.float32)),
    )
    assert type(clone) is type(model) and clone.factors is grown
    if engine != "ecommerce":
        assert clone.serve_dtype == "int8"
    if engine != "similarproduct":
        assert len(clone.item_categories) == N_ITEMS + 2
        assert clone.item_categories[-1] == frozenset()


# -- refusal, accounting, release -------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_a_state_over_one_devices_budget_names_shard_serving(
        engine, monkeypatch):
    model, answer = _served(engine)
    monkeypatch.setenv("PIO_SERVE_HBM_BYTES", "64")
    with pytest.raises(OversizedModelError, match="shard_serving"):
        model.resident.get()
    assert model.resident.device_bytes() is None
    monkeypatch.setenv("PIO_SERVE_HBM_BYTES", str(1 << 30))
    assert answer(model)


def test_resident_device_bytes_has_its_three_answers(mesh_devices):
    f = _factors()
    model = reco.ALSModel(f, serve_dtype="int8")
    host = float(f.user_factors.nbytes + f.item_factors.nbytes)
    assert model.resident_device_bytes() == host  # nothing staged
    sv = model.resident.get()
    assert model.resident_device_bytes() == sv.device_nbytes() < host
    srt = model.resident.get(shard=True)
    assert model.resident_device_bytes() == \
        srt.device_bytes()["per_shard"]
    assert model.sharded_info()["shards"] == mesh_devices


def test_assigning_none_to_serving_state_releases_every_device_buffer():
    """benchmarks/serving.py:283 frees serve-steady's slabs with
    `model._serving_state = None` before its reference takes the chip."""
    model = reco.ALSModel(_factors(), serve_dtype="int8")
    sv = model.resident.get()
    buffers = [weakref.ref(a) for a in (
        sv.users, sv.items, sv.user_scale, sv.item_scale, sv.item_inv_norm)]
    del sv
    gc.collect()
    assert all(r() is not None for r in buffers)  # the model holds them
    model._serving_state = None
    gc.collect()
    assert all(r() is None for r in buffers)
    assert model.resident.device_bytes() is None
    with pytest.raises(AttributeError):
        model._serving_state  # write-only: nothing reads the slot
    # and the next query restages
    assert model.resident.recommend([0], 3)[1].shape == (1, 3)
