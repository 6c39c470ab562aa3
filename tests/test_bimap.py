"""BiMap / EntityMap (data/store/bimap.py): an immutable two-way map whose
inverse shares its dicts (ISSUE 26) while no caller can reach them."""

import pickle
import tracemalloc

import numpy as np
import pytest

from predictionio_tpu.data.store.bimap import BiMap, EntityMap


def _string_int(n):
    return BiMap.string_int(f"id{i}" for i in range(n))


MAPS = {
    "string_int": lambda: _string_int(50),
    "int_keys": lambda: BiMap({i: i * i for i in range(1, 40)}),
    "tuple_keys": lambda: BiMap({(i, "x"): f"v{i}" for i in range(10)}),
    "empty": lambda: BiMap({}),
}


@pytest.fixture(params=sorted(MAPS))
def m(request):
    return MAPS[request.param]()


def test_inverse_of_a_large_map_allocates_nothing_of_its_size():
    big = _string_int(200_000)
    tracemalloc.start()
    try:
        before, _ = tracemalloc.get_traced_memory()
        inv = big.inverse()
        after, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak - before < 64 * 1024, peak - before
    assert after - before < 64 * 1024
    assert len(inv) == 200_000
    assert all(inv(i) == f"id{i}" for i in range(200_000))
    assert inv.get(200_000) is None and 200_000 not in inv


def test_inverse_twice_is_the_map(m):
    inv = m.inverse()
    assert inv.inverse() == m
    assert len(inv) == len(m)
    for k, v in m.items():
        assert inv(v) == k and inv.inverse()(k) == v
    # the inverse is a BiMap like any other: its own builders work on it
    assert inv.take(list(inv)[:3]).to_dict() == dict(list(inv.items())[:3])


def test_constructor_copies_the_callers_mapping():
    d = {"a": 0, "b": 1}
    bm = BiMap(d)
    inv = bm.inverse()
    d["c"] = 2
    d["a"] = 7
    assert bm.to_dict() == {"a": 0, "b": 1}
    assert inv.to_dict() == {0: "a", 1: "b"}
    assert "c" not in bm and bm("a") == 0


@pytest.mark.parametrize("side", ["map", "inverse", "inverse_of_inverse"])
def test_to_dict_is_fresh_on_either_side(side):
    bm = _string_int(20)
    target = {
        "map": bm, "inverse": bm.inverse(),
        "inverse_of_inverse": bm.inverse().inverse(),
    }[side]
    d = target.to_dict()
    assert d == dict(target.items())
    d.clear()
    d["stray"] = "stray"
    assert len(bm) == 20 and len(bm.inverse()) == 20
    assert "stray" not in bm and "stray" not in bm.inverse()
    assert all(bm.inverse()(bm(k)) == k for k in bm)
    assert target.to_dict() is not target.to_dict()


def test_duplicate_values_are_refused_and_inverse_cannot_skip_the_check():
    with pytest.raises(ValueError):
        BiMap({"a": 0, "b": 0})
    # no public way to hand the constructor a ready reverse dict
    with pytest.raises(TypeError):
        BiMap({"a": 0}, {0: "a"})


@pytest.mark.parametrize("protocol", [2, pickle.HIGHEST_PROTOCOL])
def test_pickle_round_trip_keeps_both_directions(m, protocol):
    m2, inv2 = pickle.loads(pickle.dumps((m, m.inverse()), protocol))
    assert m2 == m and inv2 == m.inverse()
    assert inv2.inverse() == m2
    for k, v in m.items():
        assert m2(k) == v and inv2(v) == k
    # alone, an inverse pickles to a whole map too
    alone = pickle.loads(pickle.dumps(m.inverse(), protocol))
    assert alone.inverse() == m


def test_map_array_reads_through_an_inverse_of_an_inverse():
    bm = _string_int(10)
    again = bm.inverse().inverse()
    out = again.map_array(["id3", "nope", "id0"])
    assert out.dtype == np.int32 and out.tolist() == [3, -1, 0]


@pytest.mark.parametrize("n", [1, 7, 300])
def test_entity_map_entity_of_agrees_with_index_of(n):
    em = EntityMap({f"e{i}": {"n": i} for i in range(n)})
    assert len(em) == n
    for i in range(n):
        eid = f"e{i}"
        assert em.entity_of(em.index_of(eid)) == eid
        assert em.index_of(em.entity_of(i)) == i
        assert em[eid] == {"n": i}
    with pytest.raises(KeyError):
        em.entity_of(n)
