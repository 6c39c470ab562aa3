"""Compile-only TPU lowering of every kernel variant the serving and training
paths can select (PR 21).

libtpu ships with the installation, so `jax.experimental.topologies` gives
a compile-only v5e client even though the suite is pinned to the CPU: each
program below is lowered from `ShapeDtypeStruct`s placed on the topology's
devices and COMPILED by the real Mosaic / XLA:TPU pipelines. No chip, no
execution — this catches what interpret mode cannot see: block shapes the
Pallas TPU lowering refuses, primitives Mosaic has no rule for, shard_map
programs that do not partition. (It would have caught the packed-mask
block — `(B, tile/32)` of a `(B, I_p/32)` array — on the day it was
written.)
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

try:
    import libtpu  # noqa: F401
except ImportError:  # pragma: no cover - the installation has it
    warnings.warn(
        "libtpu cannot be imported: EVERY TPU lowering check in "
        "tests/test_tpu_lowering.py is SKIPPED — no kernel variant is "
        "being compiled for the chip by this run"
    )
    pytest.skip(
        "libtpu not importable — TPU lowering NOT checked",
        allow_module_level=True,
    )

from predictionio_tpu.models import als  # noqa: E402
from predictionio_tpu.ops import recommend_pallas as rp  # noqa: E402
from predictionio_tpu.ops import windowed_pallas  # noqa: E402
from predictionio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS  # noqa: E402

#: ML-20M's padded serving catalog: 53 tiles of 512 (multi-tile)
I_P, RANK, TOPK = rp.pad_items(26_744), 10, 128


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """Executables of the compile-only client cannot be deserialized, so
    caching them only litters the cache directory (an in-process `pio
    train` earlier in the session may have enabled it) and warns on the
    next run's read."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices


def _on(sharding):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return sds


def _like(args, sds):
    """ShapeDtypeStructs on the topology for CPU-staged example arrays."""
    return tuple(None if a is None else sds(a.shape, a.dtype) for a in args)


@pytest.mark.parametrize("batch", [1, 8, 64])
@pytest.mark.parametrize("mask_kind", ["none", "bits", "rows"])
@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
def test_fused_recommend_compiles(v5e, dtype, mask_kind, batch):
    sds = _on(SingleDeviceSharding(v5e[0]))
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}[dtype]
    scales = (None, None)
    if dtype == "int8":
        scales = (sds((batch, 1), jnp.float32), sds((1, I_P), jnp.float32))
    bits = sds((batch, I_P // 32), jnp.int32) if mask_kind == "bits" else None
    rows = sds((batch, 8), jnp.int32) if mask_kind == "rows" else None
    rp.fused_recommend_topk.lower(
        sds((batch, RANK), dt), sds((I_P, RANK), dt), *scales, bits, rows,
        k=TOPK, n_items=sds((), jnp.int32),
    ).compile()


@pytest.mark.parametrize("batch", [1, 8, 64])
@pytest.mark.parametrize("form", ["none", "rows8", "rows64", "bits"])
@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_fused_recommend_compiles_at_the_widest_tile(v5e, dtype, form, batch):
    """Rank 128 over one shard's slab of the 19.7 M catalogue as
    `pad_items` stages it since ISSUE 31: 2,405 tiles of 2,048 rows, a
    1 MB f32 factor tile and a 512 KB (64, 2048) score tile a step."""
    i_p = rp.pad_items(19_700_000, 4) // 4
    assert rp.pick_item_tile(i_p) == 2048
    sds = _on(SingleDeviceSharding(v5e[0]))
    dt = jnp.int8 if dtype == "int8" else jnp.float32
    scales = (None, None)
    if dtype == "int8":
        scales = (sds((batch, 1), jnp.float32), sds((1, i_p), jnp.float32))
    bits = sds((batch, i_p // 32), jnp.int32) if form == "bits" else None
    rows = (sds((batch, int(form[4:])), jnp.int32)
            if form.startswith("rows") else None)
    rp.fused_recommend_topk.lower(
        sds((batch, 128), dt), sds((i_p, 128), dt), *scales, bits, rows,
        k=TOPK, n_items=sds((), jnp.int32),
    ).compile()


@pytest.mark.parametrize("mask_kind", ["bits", "rows"])
def test_fused_masked_topk_compiles_at_ur_catalog(v5e, mask_kind):
    """The CCO/universal tail at the 10^5-item catalog (tile 2,048)."""
    sds = _on(SingleDeviceSharding(v5e[0]))
    i_p = rp.pad_items(100_000)
    bits = sds((8, i_p // 32), jnp.int32) if mask_kind == "bits" else None
    rows = sds((8, 64), jnp.int32) if mask_kind == "rows" else None
    rp.fused_masked_topk.lower(
        sds((8, i_p), jnp.float32), bits, rows,
        k=TOPK, n_items=sds((), jnp.int32),
    ).compile()


def _jitted(program):
    """The jit object under the devprof wrapper (its donation and static
    arguments as the program declares them)."""
    return program.__wrapped__


UR_ITEMS, UR_TOP_N, UR_INDICATORS = 4_162_024, 50, 4


@pytest.mark.parametrize("batch,form,width", [
    (1, "mask", None), (8, "rows", 8), (8, "rows", 64), (64, "none", None)])
def test_ur_scoring_program_fits_the_chip_at_the_taobao_catalogue(
    v5e, batch, form, width
):
    """The UR serving program at the benchmark cell's shape — four
    indicators' inverted tables of 4,162,024 items x 50, a call's windows
    of postings scattered into the (B, I_p) total, the fused tail in each
    exclusion form: it compiles, takes the resident tables as they lie (no
    per-batch copy of one) and fits the chip beside them."""
    from predictionio_tpu.models import cco

    sds = _on(SingleDeviceSharding(v5e[0]))
    rows = rp.pad_items(UR_ITEMS)
    assert rows == 4_163_584 and rows % 2048 == 0
    postings = (UR_INDICATORS * UR_ITEMS * UR_TOP_N + cco._WINDOW,)
    resident = postings[0] * 8
    windows = cco.call_windows(batch)
    excluded = {"none": 0, "rows": batch * (width or 0),
                "mask": batch * (rows // 32)}[form]
    mem = _jitted(cco._score_topk_jit).lower(
        sds((batch * rows,), jnp.float32),
        sds(postings, jnp.int32), sds(postings, jnp.float32),
        sds((windows * 3 + excluded,), jnp.int32), sds((), jnp.int32),
        bsz=batch, windows=windows, rows_padded=rows, k=64, mode="tpu",
        form=form,
    ).compile().memory_analysis()
    total = batch * rows * 4
    assert resident <= mem.argument_size_in_bytes - total <= 1.01 * resident
    assert mem.temp_size_in_bytes < 0.5 * resident
    assert mem.alias_size_in_bytes >= total  # the scatter adds in place
    assert resident + mem.temp_size_in_bytes + 2 * total < 15.75e9


@pytest.mark.parametrize("mask_kind", ["none", "bits", "rows"])
@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_sharded_recommend_compiles_on_four_shards(v5e, dtype, mask_kind):
    from predictionio_tpu.fleet.runtime import _sharded_recommend

    mesh = Mesh(np.array(v5e), (MODEL_AXIS,))
    rows_sh = _on(NamedSharding(mesh, P(MODEL_AXIS, None)))
    cols_sh = _on(NamedSharding(mesh, P(None, MODEL_AXIS)))
    rep = _on(NamedSharding(mesh, P()))
    # as ShardedRuntime stages it: 6,784 rows a shard, 53 tiles of 128
    i_p = rp.pad_items(26_744, 4)
    u_p = 138_496
    dt = jnp.int8 if dtype == "int8" else jnp.float32
    scales = (None, None)
    if dtype == "int8":
        scales = (rows_sh((u_p, 1), jnp.float32), cols_sh((1, i_p), jnp.float32))
    bits = cols_sh((8, i_p // 32), jnp.int32) if mask_kind == "bits" else None
    # a row list crosses replicated; each shard renumbers it (ISSUE 29)
    excl = rep((8, 8), jnp.int32) if mask_kind == "rows" else None
    _sharded_recommend.lower(
        rep((8,), jnp.int32), rows_sh((u_p, RANK), dt),
        rows_sh((i_p, RANK), dt), *scales, bits, excl,
        k=TOPK, n_items=26_744, mesh=mesh, mode="tpu",
    ).compile()


@pytest.mark.parametrize("rank", [8, 10, 32])
def test_windowed_block_partials_compiles(v5e, rank):
    sds = _on(SingleDeviceSharding(v5e[0]))
    cb, b_e = 8, 2048
    windowed_pallas.block_partials.lower(
        sds((cb, rank, b_e), jnp.float32), sds((cb, b_e), jnp.float32),
        sds((cb, b_e), jnp.float32), sds((cb, b_e), jnp.int32),
    ).compile()


def _small_edges():
    rng = np.random.RandomState(0)
    n_users, n_items = 3000, 500
    keys = np.unique(rng.randint(0, n_users * n_items, 40_000))
    return (
        (keys // n_items).astype(np.int32), (keys % n_items).astype(np.int32),
        rng.randint(1, 6, len(keys)).astype(np.float32), n_users, n_items,
    )


@pytest.mark.parametrize("pallas_mode", [None, "tpu"])
def test_windowed_train_compiles(v5e, pallas_mode):
    rows, cols, vals, n_users, n_items = _small_edges()
    staged = als.stage_windowed(
        rows, cols, vals, n_users, n_items, als.ALSParams(iterations=2)
    )
    sds = _on(SingleDeviceSharding(v5e[0]))
    als._train_jit_windowed.lower(
        *_like(staged.device_args, sds),
        **dict(staged.static_kwargs, pallas_mode=pallas_mode),
    ).compile()


@pytest.mark.parametrize("pallas_mode", [None, "tpu"])
def test_dense_train_compiles(v5e, pallas_mode):
    rows, cols, vals, n_users, n_items = _small_edges()
    staged = als.stage_dense(
        rows, cols, vals, n_users, n_items, als.ALSParams(iterations=2)
    )
    assert staged.static_kwargs["dense_dtype"] == "int8"
    sds = _on(SingleDeviceSharding(v5e[0]))
    kwargs = {k: v for k, v in staged.static_kwargs.items() if k != "mesh"}
    als._train_jit_dense.lower(
        *_like(staged.device_args, sds),
        **dict(kwargs, pallas_mode=pallas_mode),
    ).compile()


@pytest.mark.parametrize("dp,mp", [(4, 1), (2, 2)])
def test_dense_sharded_train_compiles_on_four_chips(v5e, dp, mp):
    mesh = Mesh(np.array(v5e).reshape(dp, mp), (DATA_AXIS, MODEL_AXIS))
    n_u_p, n_i_p = 8192, 512
    r_spec = P(DATA_AXIS, MODEL_AXIS) if mp > 1 else P(DATA_AXIS, None)
    ideg_spec = P(MODEL_AXIS) if mp > 1 else P()

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    als._train_jit_dense_sharded.lower(
        sds((n_u_p, n_i_p), jnp.int8, r_spec),
        sds((n_u_p,), jnp.float32, P(DATA_AXIS)),
        sds((n_i_p,), jnp.float32, ideg_spec),
        rank=RANK, iterations=2, implicit=True, lam=0.01, alpha=1.0,
        cg_iterations=3, seed=3, dense_dtype="int8", scale=1.0, mesh=mesh,
    ).compile()


@pytest.mark.parametrize(
    "n_pairs,n_rows_p,n_cols_p,most",
    [
        (20_000_263, 139_264, 26_880, 6e9),  # ML-20M
        # the train cell's own shape (als-netflix-implicit-r10)
        (56_900_000, 464_896, 17_920, 10.5e9),
    ],
    ids=["ml20m", "netflix_cell"],
)
def test_densify_stays_within_the_chip(v5e, n_pairs, n_rows_p, n_cols_p, most):
    """The rating matrix must stage on a 16 GB chip with room for the
    train: a one-shot 2-D scatter needed a 10.2 GB lane-padded index
    temporary at ML-20M (14.4 GB in all, PR 21); the block build's
    temporaries are one row block and one chunk of slots."""
    from predictionio_tpu.ops.dense import ROW_BLOCK, densify

    sds = _on(SingleDeviceSharding(v5e[0]))
    mem = densify.lower(
        sds((n_pairs,), jnp.int32), sds((n_pairs,), jnp.int8),
        sds((n_rows_p // ROW_BLOCK + 1,), jnp.int32),
        n_rows_p=n_rows_p, n_cols_p=n_cols_p,
    ).compile().memory_analysis()
    total = (
        mem.temp_size_in_bytes + mem.output_size_in_bytes
        + mem.argument_size_in_bytes
    )
    assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes
    assert total < most, total
