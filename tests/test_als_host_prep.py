"""ALS train host prep (models/als.py `_degrees`, `dense_eligible`,
`stage_dense`; ops/dense.py `int8_scale`): every pass over a job's pairs
is linear (or one in-place sort) and done once a job, and what a job
trains is what the passes they replaced (kept here as oracles) gave, to
the bit.
"""

import time

import numpy as np
import pytest

from predictionio_tpu.models import als
from predictionio_tpu.obs.spans import get_default_recorder
from predictionio_tpu.ops import dense as dense_ops

N_USERS, N_ITEMS = 300, 180


def add_at_degrees(rows, cols, n_users, n_items):
    """The two `np.add.at` passes `_degrees` replaced."""
    user_deg = np.zeros(n_users, np.float32)
    np.add.at(user_deg, rows, 1.0)
    item_deg = np.zeros(n_items, np.float32)
    np.add.at(item_deg, cols, 1.0)
    return user_deg, item_deg


def whole_array_int8_scale(vals):
    """`int8_scale` as it was: a float64 copy of the whole array a scale."""
    m = float(np.max(np.abs(vals))) if len(vals) else 0.0
    if m == 0.0:
        return 1.0
    for s in (1.0, 2.0, 4.0, 8.0, 10.0, 16.0, 20.0, 32.0, 50.0, 64.0, 100.0):
        scaled = np.asarray(vals, np.float64) * s
        if m * s <= 127.0 and np.all(scaled == np.round(scaled)):
            return s
    return None


def unique_coo(n_edges=6000, seed=0):
    rng = np.random.RandomState(seed)
    key = rng.choice(N_USERS * N_ITEMS, n_edges, replace=False)
    rows = (key // N_ITEMS).astype(np.int32)
    cols = (key % N_ITEMS).astype(np.int32)
    vals = (rng.randint(1, 11, n_edges) / 2.0).astype(np.float32)
    return rows, cols, vals


def spans_since(name, t0):
    """The spans called `name` that ended at or after `t0` (time.time())."""
    return [sp for sp in get_default_recorder().recent(t0) if sp.name == name]


# -- (a) degrees --------------------------------------------------------------


def _degree_case(name):
    rng = np.random.RandomState(7)
    if name == "empty":
        return np.zeros(0, np.int64), np.zeros(0, np.int64), 5, 3
    if name == "ids_that_never_occur":
        return np.array([0, 2, 2, 9]), np.array([1, 1, 6, 6]), 12, 8
    if name == "one_hot_id":
        return np.full(5000, 3), np.full(5000, 0), 4, 2
    if name == "random_1e5":
        return rng.randint(0, 4000, 100_000), rng.randint(0, 700, 100_000), 4000, 700
    if name == "several_chunks":
        return rng.randint(0, 50, 2500), rng.randint(0, 20, 2500), 50, 20
    raise AssertionError(name)


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize(
    "case",
    ["empty", "ids_that_never_occur", "one_hot_id", "random_1e5", "several_chunks"],
)
def test_degrees_equal_the_add_at_passes(monkeypatch, case, dtype):
    rows, cols, n_users, n_items = _degree_case(case)
    rows, cols = rows.astype(dtype), cols.astype(dtype)
    if case == "several_chunks":
        monkeypatch.setattr(als, "_DEGREE_CHUNK", 1000)
    user_deg, item_deg = als._degrees(rows, cols, n_users, n_items)
    ref_u, ref_i = add_at_degrees(rows, cols, n_users, n_items)
    assert user_deg.dtype == item_deg.dtype == np.float32
    assert user_deg.shape == (n_users,) and item_deg.shape == (n_items,)
    np.testing.assert_array_equal(user_deg, ref_u)
    np.testing.assert_array_equal(item_deg, ref_i)


@pytest.mark.parametrize("side", ["user", "item"])
def test_degrees_refuse_an_id_outside_its_table(side):
    rows, cols = np.array([0, 1, 2]), np.array([0, 1, 2])
    n_users, n_items = (2, 3) if side == "user" else (3, 2)
    with pytest.raises(IndexError):
        add_at_degrees(rows, cols, n_users, n_items)
    with pytest.raises(IndexError):
        als._degrees(rows, cols, n_users, n_items)


# -- (b) int8_scale -----------------------------------------------------------

_LONG = 3 * dense_ops._SCALE_CHUNK + 17  # a ragged last chunk


def _scale_case(name):
    rng = np.random.RandomState(11)
    if name == "all_ones":
        return np.ones(_LONG, np.float32)
    if name == "half_stars":
        return (rng.randint(1, 11, _LONG) / 2.0).astype(np.float32)
    if name == "tenth_steps":
        return (rng.randint(1, 51, 4000) / 10.0).astype(np.float32)
    if name == "tenth_steps_float64":
        return rng.randint(1, 51, 4000) / 10.0
    if name == "quarter_steps_float64":
        return rng.randint(-40, 41, _LONG) / 4.0
    if name == "counts_to_127":
        return np.arange(128, dtype=np.float32)
    if name == "counts_to_128":
        return np.arange(129, dtype=np.float32)
    if name == "negatives":
        return -(rng.randint(1, 11, 4000) / 2.0).astype(np.float32)
    if name == "negative_128":
        return np.array([1.0, -128.0], np.float32)
    if name == "zeros_only":
        return np.zeros(4000, np.float32)
    if name == "empty":
        return np.zeros(0, np.float32)
    if name == "one_bad_value_in_the_last_chunk":
        vals = np.ones(_LONG, np.float32)
        vals[-1] = 0.3
        return vals
    if name == "one_half_in_the_last_chunk":
        vals = np.ones(_LONG, np.float32)
        vals[-1] = 2.5
        return vals
    if name == "fiftieths":
        return (rng.randint(1, 100, 4000) / 50.0).astype(np.float64)
    if name == "integer_dtype":
        return rng.randint(0, 100, 4000)
    if name == "nan":
        return np.array([1.0, np.nan, 2.0], np.float32)
    if name == "inf":
        return np.array([1.0, np.inf], np.float32)
    raise AssertionError(name)


@pytest.mark.parametrize(
    "case",
    [
        "all_ones", "half_stars", "tenth_steps", "tenth_steps_float64",
        "quarter_steps_float64", "counts_to_127", "counts_to_128", "negatives",
        "negative_128", "zeros_only", "empty", "one_bad_value_in_the_last_chunk",
        "one_half_in_the_last_chunk", "fiftieths", "integer_dtype", "nan", "inf",
    ],
)
def test_int8_scale_returns_what_the_whole_array_form_did(case):
    vals = _scale_case(case)
    with np.errstate(invalid="ignore"):
        expected = whole_array_int8_scale(vals)
    got = dense_ops.int8_scale(vals)
    assert got == expected
    assert type(got) is type(expected)


def test_int8_scale_makes_no_copy_the_size_of_its_input(monkeypatch):
    """Every array it allocates is a chunk's, whatever the input's size."""
    vals = (np.arange(_LONG) % 10 / 2.0).astype(np.float32)
    biggest = []
    real_rint = np.rint

    def rint(x, *a, **kw):
        biggest.append(x.size)
        return real_rint(x, *a, **kw)

    monkeypatch.setattr(np, "rint", rint)
    assert dense_ops.int8_scale(vals) == 2.0
    # scale 1 fails in its first chunk (0.5 is there); scale 2 reads all four
    assert len(biggest) == 1 + 4
    assert max(biggest) == dense_ops._SCALE_CHUNK


# -- (c) the gate's verdict, returned and on its span -------------------------


# where the pair's other occurrence is written, by case
_DUPLICATE_AT = {
    "duplicate_pairs_first": 0, "duplicate_pairs_middle": 250,
    "duplicate_pairs_last": -1, "duplicate_pairs_zero": 250,
}


def _gate_case(name, monkeypatch):
    """(rows, cols, vals, params, kwargs) and the environment for `name`."""
    rows, cols, vals = unique_coo(500, seed=3)
    params = als.ALSParams(rank=8)
    kwargs = {}
    monkeypatch.setenv("PIO_DENSE_ALS", "1")
    if name == "env_off":
        monkeypatch.setenv("PIO_DENSE_ALS", "0")
    elif name == "rank":
        params = als.ALSParams(rank=als.GRAM_SOLVER_MAX_RANK + 8)
    elif name == "multi_process":
        import jax

        monkeypatch.setattr(jax, "process_count", lambda: 2)
        kwargs["mesh"] = object()
    elif name == "few_edges":
        monkeypatch.delenv("PIO_DENSE_ALS")
    elif name == "bytes":
        monkeypatch.setenv("PIO_DENSE_ALS_BYTES", "1000")
    elif name == "explicit_zero":
        params = als.ALSParams(rank=8, implicit_prefs=False)
        vals = vals.copy()
        vals[17] = 0.0
    elif name in _DUPLICATE_AT:
        at = _DUPLICATE_AT[name] % len(rows)
        src = (at + 123) % len(rows)
        rows, cols, vals = rows.copy(), cols.copy(), vals.copy()
        rows[at], cols[at] = rows[src], cols[src]
        if name == "duplicate_pairs_zero":
            # the pair's second occurrence is worth 0: a count of the
            # densified matrix's non-zeros would not see it
            vals[max(at, src)] = 0.0
    elif name == "eligible_not_quantizable":
        vals = vals.copy()
        vals[5] = 0.3
    elif name != "eligible":
        raise AssertionError(name)
    return rows, cols, vals, params, kwargs


@pytest.mark.parametrize(
    "case,verdict,dense_dtype,scale",
    [
        ("env_off", "env_off", None, None),
        ("rank", "rank", None, None),
        ("multi_process", "multi_process", None, None),
        ("few_edges", "few_edges", None, None),
        ("bytes", "bytes", "int8", 2.0),
        ("explicit_zero", "explicit_zero", "int8", 2.0),
        ("duplicate_pairs_first", "duplicate_pairs", "int8", 2.0),
        ("duplicate_pairs_middle", "duplicate_pairs", "int8", 2.0),
        ("duplicate_pairs_last", "duplicate_pairs", "int8", 2.0),
        ("duplicate_pairs_zero", "duplicate_pairs", "int8", 2.0),
        ("eligible", "eligible", "int8", 2.0),
        ("eligible_not_quantizable", "eligible", "bf16", None),
    ],
)
def test_gate_verdict_and_span_attrs(monkeypatch, case, verdict, dense_dtype, scale):
    rows, cols, vals, params, kwargs = _gate_case(case, monkeypatch)
    before = time.time()
    gate = als.dense_eligible(rows, cols, vals, N_USERS, N_ITEMS, params, **kwargs)
    assert bool(gate) is (verdict == "eligible")
    assert gate.verdict == verdict
    assert gate.dense_dtype == dense_dtype
    assert gate.int8_scale == scale
    assert gate.scale_known is (dense_dtype is not None)
    [sp] = spans_since("als.train.dense_eligible", before)
    assert sp.attrs == {
        "pairs": len(rows), "verdict": verdict,
        "dense_dtype": dense_dtype, "int8_scale": scale,
        "sort_kept": verdict == "eligible",
    }
    assert (gate.pairs is not None) is (verdict == "eligible")


def test_gate_leaves_its_inputs_as_they_were(monkeypatch):
    """The key is built and sorted in a buffer of the gate's own."""
    rows, cols, vals, params, _ = _gate_case("eligible", monkeypatch)
    kept = rows.copy(), cols.copy(), vals.copy()
    assert als.dense_eligible(rows, cols, vals, N_USERS, N_ITEMS, params)
    for now, then in zip((rows, cols, vals), kept):
        np.testing.assert_array_equal(now, then)


def test_a_gate_that_was_told_the_dtype_does_not_scan_for_a_scale(monkeypatch):
    rows, cols, vals, params, _ = _gate_case("eligible", monkeypatch)
    monkeypatch.setattr(
        dense_ops, "int8_scale", lambda v: pytest.fail("scanned the ratings")
    )
    gate = als.dense_eligible(
        rows, cols, vals, N_USERS, N_ITEMS, params, dense_dtype="f32"
    )
    assert gate and gate.dense_dtype == "f32" and not gate.scale_known


# -- (d) once a job -----------------------------------------------------------


@pytest.fixture
def passes(monkeypatch):
    """Counts the calls of the two passes over a job's pairs."""
    calls = {"int8_scale": 0, "degrees": 0}
    real_scale, real_degrees = dense_ops.int8_scale, als._degrees

    def scale(vals):
        calls["int8_scale"] += 1
        return real_scale(vals)

    def degrees(*a):
        calls["degrees"] += 1
        return real_degrees(*a)

    monkeypatch.setattr(dense_ops, "int8_scale", scale)
    monkeypatch.setattr(als, "_degrees", degrees)
    monkeypatch.setenv("PIO_DENSE_ALS", "1")
    return calls


def test_a_dense_train_job_scans_for_its_scale_and_degrees_once(passes):
    rows, cols, vals = unique_coo(800, seed=4)
    before = time.time()
    m = als.train(
        rows, cols, vals, N_USERS, N_ITEMS, als.ALSParams(rank=6, iterations=2)
    )
    assert passes == {"int8_scale": 1, "degrees": 1}
    [sp] = spans_since("als.stage.host_prep", before)
    assert sp.attrs["int8_scale_reused"] is True
    assert sp.attrs["pairs_grouped_reused"] is True
    assert sp.attrs["degrees_reused"] is True
    assert np.all(np.isfinite(m.user_factors))


def test_a_windowed_train_job_computes_its_degrees_once(passes, monkeypatch):
    monkeypatch.setenv("PIO_DENSE_ALS", "0")
    rows, cols, vals = unique_coo(800, seed=4)
    before = time.time()
    als.train(rows, cols, vals, N_USERS, N_ITEMS, als.ALSParams(rank=6, iterations=2))
    assert passes == {"int8_scale": 0, "degrees": 1}
    [sp] = spans_since("als.stage.host_prep", before)
    assert sp.attrs == {"degrees_reused": True}


@pytest.mark.parametrize("path", ["dense", "windowed"])
def test_train_grid_stages_with_no_degrees_handed_in(passes, monkeypatch, path):
    rows, cols, vals = unique_coo(800, seed=5)
    if path == "windowed":
        monkeypatch.setenv("PIO_DENSE_ALS", "0")
    grid = [als.ALSParams(rank=6, iterations=2, lambda_=lam) for lam in (0.01, 0.1)]
    before = time.time()
    out = als.train_grid(rows, cols, vals, N_USERS, N_ITEMS, grid)
    assert len(out) == 2 and all(np.all(np.isfinite(f.user_factors)) for f in out)
    assert passes == {"int8_scale": int(path == "dense"), "degrees": 1}
    [sp] = spans_since("als.stage.host_prep", before)
    assert sp.attrs["degrees_reused"] is False
    if path == "dense":
        assert sp.attrs["int8_scale_reused"] is True
        assert sp.attrs["pairs_grouped_reused"] is True


@pytest.mark.parametrize("dense_dtype", ["auto", "int8", "f32"])
def test_a_bare_stage_dense_finds_its_own_scale_and_degrees(passes, dense_dtype):
    rows, cols, vals = unique_coo(800, seed=6)
    before = time.time()
    staged = als.stage_dense(
        rows, cols, vals, N_USERS, N_ITEMS, als.ALSParams(rank=6, iterations=2),
        dense_dtype=dense_dtype,
    )
    assert passes == {"int8_scale": int(dense_dtype != "f32"), "degrees": 1}
    assert staged.static_kwargs["dense_dtype"] == (
        "f32" if dense_dtype == "f32" else "int8"
    )
    assert staged.static_kwargs["scale"] == (1.0 if dense_dtype == "f32" else 2.0)
    [sp] = spans_since("als.stage.host_prep", before)
    assert sp.attrs == {
        "int8_scale_reused": False, "pairs_grouped_reused": False,
        "degrees_reused": False,
    }


def test_stage_dense_takes_the_gates_scale_as_it_would_find_it(passes):
    """Handed the gate, the staged train is the one a bare call stages."""
    rows, cols, vals = unique_coo(800, seed=6)
    p = als.ALSParams(rank=6, iterations=2)
    gate = als.dense_eligible(rows, cols, vals, N_USERS, N_ITEMS, p)
    assert passes["int8_scale"] == 1
    handed = als.stage_dense(rows, cols, vals, N_USERS, N_ITEMS, p, gate=gate)
    assert passes["int8_scale"] == 1
    bare = als.stage_dense(rows, cols, vals, N_USERS, N_ITEMS, p)
    assert passes["int8_scale"] == 2
    assert handed.static_kwargs == bare.static_kwargs
    for a, b in zip(handed.device_args, bare.device_args):
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stage_dense_refuses_int8_for_ratings_the_gate_found_unquantizable(passes):
    rows, cols, vals = unique_coo(800, seed=6)
    vals[3] = 0.3
    p = als.ALSParams(rank=6, iterations=2)
    gate = als.dense_eligible(rows, cols, vals, N_USERS, N_ITEMS, p)
    assert gate and gate.dense_dtype == "bf16"
    with pytest.raises(ValueError, match="int8"):
        als.stage_dense(
            rows, cols, vals, N_USERS, N_ITEMS, p, dense_dtype="int8", gate=gate
        )
    staged = als.stage_dense(rows, cols, vals, N_USERS, N_ITEMS, p, gate=gate)
    assert staged.static_kwargs["dense_dtype"] == "bf16"
    assert passes["int8_scale"] == 1


# -- (e) the factors, to the bit ----------------------------------------------


def _train_path_case(path, monkeypatch):
    rows, cols, vals = unique_coo(1500, seed=8)
    params = als.ALSParams(rank=6, iterations=3)
    monkeypatch.setenv("PIO_DENSE_ALS", "1")
    spy = {"dense": "_train_dense", "windowed": "_train_windowed", "scatter": None}[path]
    if path == "windowed":
        # a pair twice: the gate refuses, the windowed path sums the two
        rows = np.concatenate([rows, rows[:1]])
        cols = np.concatenate([cols, cols[:1]])
        vals = np.concatenate([vals, vals[:1]])
    elif path == "scatter":
        params = als.ALSParams(rank=40, iterations=2)
    return rows, cols, vals, params, spy


@pytest.mark.parametrize("path", ["dense", "windowed", "scatter"])
def test_factors_are_bit_equal_to_a_train_on_add_at_degrees(monkeypatch, path):
    rows, cols, vals, params, spy = _train_path_case(path, monkeypatch)
    took = []
    for name in ("_train_dense", "_train_windowed"):
        real = getattr(als, name)
        monkeypatch.setattr(
            als, name,
            lambda *a, _real=real, _name=name, **kw: (
                took.append(_name), _real(*a, **kw))[1],
        )
    now = als.train(rows, cols, vals, N_USERS, N_ITEMS, params)
    assert took == ([spy] if spy else [])
    monkeypatch.setattr(als, "_degrees", add_at_degrees)
    then = als.train(rows, cols, vals, N_USERS, N_ITEMS, params)
    assert now.user_factors.shape == (N_USERS, params.rank)
    assert np.all(np.isfinite(now.user_factors))
    assert now.user_factors.tobytes() == then.user_factors.tobytes()
    assert now.item_factors.tobytes() == then.item_factors.tobytes()
