"""Verb-agnostic fused score+top-k: score, mask, and select in ONE pass.

The serving hot path's XLA form is a two-step program —
``scores = q @ itf.T`` then ``lax.top_k`` (models/als.py's
`_recommend_jit[_nomask]`): XLA materializes the full (B, I) score
matrix in HBM between the matmul and the TopK custom call. At serving
rank (K ≈ 10) the score matrix IS the dominant HBM term: one write plus
one read of B·I·4 bytes against an item-factor stream of only I·K·4
(for B = 64 on the ML-20M catalog that's ~14 MB of score traffic vs
~1 MB of factors — >90 % of the pass).

This kernel never materializes the score matrix. The grid walks item
tiles; each step loads one (T, K) factor tile into VMEM, issues the
(B, T) MXU dot against the resident query block, applies the exclusion
mask and the dead-pad-column mask in registers, and merges the tile
into a RUNNING sorted top-k list held in VMEM scratch. Only the final
(B, k) values + global indices ever reach HBM.

ISSUE 14 generalizes the PR-11 recommend-only kernel into the ONE
fused selector every serving verb routes through:

- **scaled scoring** (the cosine/int8 unification): optional per-row
  (B, 1) query scales and (1, I_p) item scales multiply the dot in
  registers. int8 mode uses them as dequant scales; the cosine verbs
  (`als.similar`, itemsim's on-the-fly column cosine) pass INVERSE
  NORMS — cosine(q, x) = (q·x)·(1/|q|)·(1/|x|) — so the SAME resident
  factor slab serves both dot-product recommend and cosine similar
  with no normalized copy in HBM.
- **precomputed-score mode** (`fused_masked_topk`): the CCO/universal
  `batch_score_topk` accumulates its (B, I) LLR total by gather —
  there is no factor matmul to fuse — but its exclusion + top-k tail
  is this kernel's exact shape: stream the score tiles once, mask in
  registers, running top-k in VMEM. The XLA tail's masked score COPY
  (a second B·I write+read) and the (B, I) exclusion-mask
  materialization both disappear.
- **bit-packed masks**: the exclusion mask input is a little-endian
  bit-word column (`pack_mask_np`, (B, I_p/32) int32) — 1/32 the
  host→device and HBM mask bytes of the old f32 0/1 input — expanded
  to per-lane bits in registers.
- **exclusion ROW LISTS**: the common small-blacklist case (a few
  excluded items per query) ships a (B, E) int32 index list instead of
  any per-item mask; the kernel compares global column ids against the
  E resident entries per tile. E is static and small (row-list callers
  cap at `ROWLIST_MAX`); -1 and out-of-range entries are inert.

The merge is an iterative extraction with early exit: while any query
row's tile maximum still beats that row's current k-th value, extract
each such row's (max, lowest-index-of-max) and insert it into the
row's sorted list (count-position + lane shift — no sort primitive,
Mosaic has none on this jax). For random scores the expected number of
extractions across the WHOLE pass is k·(1 + ln n_tiles) — the early
exit makes later tiles nearly free — and the worst case terminates
(every iteration kills at least one element of some live row).

Tie-breaking matches `lax.top_k` exactly (stable: among equal values
the LOWEST index wins): tiles scan in index order, within a tile the
extraction takes the lowest index of the row max, and the insertion
position counts `>=` so a later tie lands after the resident equals.
tests/test_recommend_pallas.py + tests/test_fused_serving.py prove
parity against the XLA two-step in interpret mode (masked / unmasked /
k edge cases / crafted cross-tile ties / packed-vs-rowlist
equivalence).

dtype modes: f32 (f32 storage and accumulation; on the TPU the MXU runs
a default-precision dot on bf16-rounded operands, so scores sit within
2^-8·Σ|q_k·x_k| of exact — see "On the chip" below), bf16 (bf16 storage +
bf16×bf16→f32 MXU dot — half the factor stream, scores within bf16
rounding), int8 (per-row symmetric quantization, int8×int8→int32 dot,
scale-product dequant in registers — ~1/4 the factor stream).

Gating mirrors ops/windowed_pallas.py: `resolve_mode("auto")` returns
"tpu" where jax's default backend is a TPU, "interpret" under
PIO_PALLAS_RECOMMEND=interpret (the CPU test path), else None — callers
then keep the XLA two-step (which still gets the int8/bf16, packed-mask,
and donation wins).

On the chip (v5e, jax 0.9.0, PR 21 — tests/chip_parity.py re-runs it;
tests/test_tpu_lowering.py compiles every variant against a compile-only
v5e topology in tier-1). Every variant — f32/bf16/int8 × no mask / packed
words / row list × B 1/8/64 at k = 128, and the precomputed-score tail —
compiles and runs. Against the XLA two-step on the same device: int8
matches exactly; bf16 to a few f32 ulps with identical indices; cosine
`similar` and the CCO tail exactly; crafted ties, a fully-masked row and
k == n_items keep `lax.top_k`'s order; packed words and row lists give
identical answers. f32 matches BIT-FOR-BIT at B = 8 and 64 — both paths
run the MXU on bf16-rounded operands, at worst 2^-8.1 of the row's
largest Σ|q_k·x_k| off a numpy f32 product — but NOT at B = 1, where XLA
lowers `q @ items.T` as an exact f32 matrix-vector product and the
kernel still uses the MXU: there a mode change moves f32 scores by up to
that bound and reorders near-ties (64–81 % of top-128 indices equal on
random factors). The
packed-word input was the one variant the Mosaic lowering refused, as
first written: a (B, tile/32) block of the (B, I_p/32) array is neither
(8, 128)-divisible nor the full dims (`_fused_call` relays the words
tile-major).
"""

from __future__ import annotations
from predictionio_tpu.utils.env import env_str as _env_str

import functools

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.topk import NEG_INF

#: item-tile ladder — the widest tile that divides the padded item count
#: wins (`pick_item_tile`); `pad_items` stages the table so that a wide
#: one does wherever the catalogue is large enough to afford its pad
ITEM_TILES = (2048, 1024, 512, 256, 128)
#: floor of the staging pad quantum: the narrowest tile, which always
#: qualifies (a multiple of 32, so bit-packed mask words always cover
#: whole tiles)
ITEM_PAD = 128
#: what the staging pad may add to a shard's slab to reach a wider tile:
#: at most this fraction of its rows (1.6 %). A constant beside the
#: ladder, not a knob — the kernel's pass over 4.9 M rank-128 rows is
#: 27 ms at tile 128 and 5.8 at 2,048 (v5e, B = 64, PERF.md PR 29)
PAD_WASTE = 1 / 64

#: widest (B, E) exclusion row list the kernel unrolls per tile; longer
#: exclusion sets must ship as bit-packed mask words instead (the
#: unrolled compare chain would start to rival the score matmul's cost)
ROWLIST_MAX = 64

#: running-list sentinel: strictly below every representable score
#: INCLUDING the NEG_INF mask value, so dead pad columns and the
#: not-yet-filled tail never collide with legitimately masked entries
_SENTINEL = float(jnp.finfo(jnp.float32).min)


def pick_item_tile(n_items_padded: int) -> int:
    for t in ITEM_TILES:
        if n_items_padded % t == 0:
            return t
    return 0


def pad_items(n_items: int, shards: int = 1, fused: bool = True) -> int:
    """Padded item-row count the staging side must allocate, on either
    tier: `shards` equal slabs. Where a fused mode resolved, a slab is a
    multiple of the WIDEST tile of the ladder whose pad adds at most
    `PAD_WASTE` of the slab's rows (ITEM_PAD, the narrowest, always
    qualifies), so the tile follows the catalogue's size and not its
    remainder modulo 2,048: under 8 k rows a shard the count is the
    next multiple of 128, 26,744 rows take tile 512, and from 131 k
    rows a shard every catalogue takes 2,048. On the XLA path of the
    sharded tier a slab is a multiple of 32, so the packed-mask words
    column-shard cleanly. Pad rows are zero and die under the live
    count."""
    rows = -(-max(n_items, 1) // shards)

    def slab(quantum: int) -> int:
        return -(-rows // quantum) * quantum

    if not fused:
        return slab(32) * shards
    wide = [
        slab(t) for t in ITEM_TILES if slab(t) - rows <= rows * PAD_WASTE
    ]
    return (wide[0] if wide else slab(ITEM_PAD)) * shards


# ---------------------------------------------------------------------------
# bit-packed exclusion masks (ISSUE 14 tentpole part 3)
# ---------------------------------------------------------------------------


def pack_mask_np(mask, i_p: int):
    """Host-side pack of a bool (B, n) exclusion mask into little-endian
    32-bit words at the padded item width: word ``c // 32`` bit
    ``c % 32`` is column ``c``. (B, i_p/32) int32 — 1/32 the bytes of
    the f32 0/1 mask the kernel used to take (i_p is ITEM_PAD-aligned,
    so 32 always divides it)."""
    import numpy as np

    mask = np.asarray(mask, bool)
    b = mask.shape[0]
    out = np.zeros((b, i_p // 8), np.uint8)
    if mask.shape[1]:
        packed = np.packbits(mask, axis=1, bitorder="little")
        out[:, : packed.shape[1]] = packed[:, : i_p // 8]
    return np.ascontiguousarray(out).view("<u4").view("<i4")


def rowlist_np(lists):
    """Host-side (B, E) int32 -1-padded exclusion row list from
    per-query id lists, at the shared pow2-bucketed width (floor 8) —
    the ONE owner of the row-list wire convention (width bucketing +
    pad sentinel), so the engines and the serving layer can never
    drift. Returns None when every list is empty."""
    import numpy as np

    widest = max((len(r) for r in lists), default=0)
    if widest == 0:
        return None
    e_pad = max(8, 1 << (widest - 1).bit_length())
    ex = np.full((len(lists), e_pad), -1, np.int32)
    for b, row in enumerate(lists):
        ex[b, : len(row)] = row
    return ex


def unpack_mask_jnp(words: jax.Array, n_cols: int) -> jax.Array:
    """Traced unpack of packed mask words back to a bool (B, n_cols)
    mask — the XLA fallback's read side, so packed callers carry 1/32
    the mask traffic regardless of which kernel mode resolved."""
    b, w = words.shape
    bits = jnp.broadcast_to(words[:, :, None], (b, w, 32))
    shifts = jnp.arange(32, dtype=words.dtype)[None, None, :]
    return (
        jax.lax.shift_right_logical(bits, shifts) & 1
    ).reshape(b, w * 32)[:, :n_cols] != 0


def rowlist_mask_jnp(rows: jax.Array, n_cols) -> jax.Array:
    """Traced (B, E) exclusion row list → bool (B, n_cols) mask (the
    XLA fallback's scatter; -1/-out-of-range entries inert)."""
    b = rows.shape[0]
    safe = jnp.where(
        (rows >= 0) & (rows < n_cols), rows, n_cols
    )
    m = jnp.zeros((b, n_cols + 1), bool)
    m = m.at[jnp.arange(b)[:, None], safe].set(True)
    return m[:, :n_cols]


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


def _shift_right(x: jax.Array) -> jax.Array:
    """Lane shift by one: out[:, j] = x[:, j-1] (lane 0 duplicated —
    always overwritten by the insert select)."""
    return jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)


def _make_kernel(
    *, k: int, tile: int, mask_kind, n_excl: int, scaled: bool,
    int8: bool, precomputed: bool, n_tiles: int,
):
    from jax.experimental import pallas as pl

    def kernel(*refs):
        it = iter(refs)
        n_ref = next(it)  # (1,) i32 SMEM — live item count (TRACED:
        # vocab growth within the pad must not recompile the program)
        if precomputed:
            sc_ref = next(it)  # (B, tile) f32 score tile
            q_ref = itf_ref = None
        else:
            q_ref = next(it)
            itf_ref = next(it)
        qs_ref = next(it) if scaled else None
        isc_ref = next(it) if scaled else None
        mask_ref = next(it) if mask_kind is not None else None
        vals_ref = next(it)
        idx_ref = next(it)
        rv_ref = next(it)  # (B, k) f32 running values, sorted desc
        ri_ref = next(it)  # (B, k) i32 running global indices

        j = pl.program_id(0)

        @pl.when(j == 0)
        def _init():
            rv_ref[...] = jnp.full(rv_ref.shape, _SENTINEL, jnp.float32)
            ri_ref[...] = jnp.zeros(ri_ref.shape, jnp.int32)

        # -- score tile — the only read of this factor/score tile ------
        if precomputed:
            s = sc_ref[...]
        elif int8:
            s = jax.lax.dot_general(
                q_ref[...], itf_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.float32)
        else:
            # f32 or bf16 storage; the MXU accumulates in f32 either way
            s = jax.lax.dot_general(
                q_ref[...], itf_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        if scaled:
            # dequant (int8) or inverse-norm (cosine) scale product —
            # the (B,1)·(1,T) outer product applies in registers
            s = s * qs_ref[...] * isc_ref[...]
        b = s.shape[0]
        col = jax.lax.broadcasted_iota(jnp.int32, (b, tile), 1)
        gcol0 = j * tile
        if mask_kind == "bits":
            # packed words (B, tile/32): expand each word over its 32
            # lanes and shift the lane's bit down — no f32 mask column
            w = mask_ref[...]
            bits = jnp.broadcast_to(
                w.reshape(b, tile // 32, 1), (b, tile // 32, 32)
            ).reshape(b, tile)
            bit = jax.lax.shift_right_logical(bits, col % 32) & 1
            s = jnp.where(bit != 0, NEG_INF, s)
        elif mask_kind == "rows":
            # (B, E) exclusion row list, resident: compare global column
            # ids per tile; -1 / out-of-range entries never match
            ex = mask_ref[...]
            gc = gcol0 + col
            hit = gc == ex[:, 0:1]
            for e in range(1, n_excl):
                hit = hit | (gc == ex[:, e : e + 1])
            s = jnp.where(hit, NEG_INF, s)
        # dead pad columns sink BELOW the mask value: they must lose to
        # legitimately masked real items when the list drains that deep
        s = jnp.where(gcol0 + col >= n_ref[0], _SENTINEL, s)

        lane = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)

        def body(carry):
            s, rv, ri, _ = carry
            m = jnp.max(s, axis=1, keepdims=True)  # (B, 1)
            # lowest column index attaining the row max (argmax is not
            # a Mosaic primitive; two reductions are)
            am = jnp.min(
                jnp.where(s == m, col, jnp.int32(2**30)),
                axis=1, keepdims=True,
            )
            live = m > rv[:, k - 1 : k]  # (B, 1) rows still inserting
            # sorted insert: position counts >= so ties land AFTER the
            # resident equals (earlier tiles = lower indices = stable)
            pos = jnp.sum(
                (rv >= m).astype(jnp.int32), axis=1, keepdims=True
            )
            nv = jnp.where(
                lane < pos, rv,
                jnp.where(lane == pos, m, _shift_right(rv)),
            )
            ni = jnp.where(
                lane < pos, ri,
                jnp.where(lane == pos, am + gcol0, _shift_right(ri)),
            )
            rv = jnp.where(live, nv, rv)
            ri = jnp.where(live, ni, ri)
            # kill the extracted element so the next max is fresh
            s = jnp.where((col == am) & live, _SENTINEL, s)
            cont = jnp.max(
                jnp.max(s, axis=1, keepdims=True) - rv[:, k - 1 : k]
            )
            return s, rv, ri, cont

        rv0, ri0 = rv_ref[...], ri_ref[...]
        cont0 = jnp.max(
            jnp.max(s, axis=1, keepdims=True) - rv0[:, k - 1 : k]
        )
        _, rv1, ri1, _ = jax.lax.while_loop(
            lambda c: c[3] > 0.0, body, (s, rv0, ri0, cont0)
        )
        rv_ref[...] = rv1
        ri_ref[...] = ri1

        @pl.when(j == n_tiles - 1)
        def _emit():
            vals_ref[...] = rv_ref[...]
            idx_ref[...] = ri_ref[...]

    return kernel


def _fused_call(
    *, b: int, kdim: int, n_items_p: int, k: int, item_tile: int,
    interpret: bool, precomputed: bool, scaled: bool, int8: bool,
    mask_kind, n_excl: int, n_items, main_args: list, main_specs: list,
    scale_args: list, mask_arg,
):
    """Shared pallas_call assembly for the q·itf and precomputed-score
    entry points — one place owns specs, scratch, and grid."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = item_tile or pick_item_tile(n_items_p)
    if tile <= 0:
        raise ValueError(
            f"padded item count {n_items_p} has no tile divisor — stage "
            f"with recommend_pallas.pad_items"
        )
    if not 0 < k <= n_items_p:
        raise ValueError(f"need 0 < k ({k}) <= padded {n_items_p}")
    n_tiles = n_items_p // tile

    n_arr = jnp.asarray(n_items, jnp.int32).reshape(1)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + main_specs(tile)
    args = [n_arr] + main_args
    if scaled:
        in_specs.append(pl.BlockSpec((b, 1), lambda j: (0, 0)))
        in_specs.append(pl.BlockSpec((1, tile), lambda j: (0, j)))
        args.extend(scale_args)
    if mask_kind == "bits":
        # tile-major words: a (B, tile/32) block of the (B, I_p/32) array
        # is neither (8, 128)-divisible nor the full dims, which the
        # Mosaic lowering refuses; relaid (n_tiles, B, tile/32) the
        # block's trailing dims ARE the array's. The wire/sharding form
        # stays (B, I_p/32) — this relayout is in-jit, per local slab.
        words = mask_arg.reshape(b, n_tiles, tile // 32).transpose(1, 0, 2)
        in_specs.append(
            pl.BlockSpec((None, b, tile // 32), lambda j: (j, 0, 0))
        )
        args.append(words)
    elif mask_kind == "rows":
        in_specs.append(pl.BlockSpec((b, n_excl), lambda j: (0, 0)))
        args.append(mask_arg)

    kernel = _make_kernel(
        k=k, tile=tile, mask_kind=mask_kind, n_excl=n_excl,
        scaled=scaled, int8=int8, precomputed=precomputed,
        n_tiles=n_tiles,
    )
    return pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b, k), lambda j: (0, 0)),
            pl.BlockSpec((b, k), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, k), jnp.float32),
            pltpu.VMEM((b, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(*args)


def _mask_kind(mask_bits, exclude_rows):
    if mask_bits is not None and exclude_rows is not None:
        raise ValueError(
            "pass either packed mask words or an exclusion row list, "
            "not both — callers compose exclusions into one form"
        )
    if mask_bits is not None:
        return "bits"
    if exclude_rows is not None:
        if exclude_rows.shape[1] == 0:
            # a (B, 0) list excludes nothing — the kernel's compare
            # chain cannot broadcast against a zero width
            return None
        if exclude_rows.shape[1] > ROWLIST_MAX:
            raise ValueError(
                f"exclusion row list width {exclude_rows.shape[1]} > "
                f"ROWLIST_MAX ({ROWLIST_MAX}) — pack to mask words"
            )
        return "rows"
    return None


@functools.partial(
    jax.jit,
    static_argnames=("k", "interpret", "item_tile"),
)
def fused_recommend_topk(  # lint: disable=jit-boundary — inner
    # boundary: invoked inside als.recommend_serving/similar_serving or
    # the sharded local(), all instrumented; this jit inlines into
    # their traces
    q: jax.Array,  # (B, K) f32 | bf16 | int8 — matches itf's dtype
    itf: jax.Array,  # (I_p, K) f32 | bf16 | int8
    q_scale=None,  # (B, 1) f32 per-row scales (int8 dequant / cosine 1/|q|)
    item_scale=None,  # (1, I_p) f32 per-row scales
    mask_bits=None,  # (B, I_p/32) int32 packed exclusion words
    exclude_rows=None,  # (B, E) int32 exclusion row list, -1 padded
    *,
    k: int,
    n_items,  # TRACED live item count (int or () int32 array)
    interpret: bool = False,
    item_tile: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """One-pass fused score+top-k over a padded item-factor matrix.

    Returns (values (B, k) f32, global indices (B, k) int32) with
    `lax.top_k` semantics (descending, ties to the lowest index).
    Requires k <= n_items (callers cap — models/als.py does) and
    itf.shape[0] % tile == 0 (stage with `pad_items`). `n_items` rides
    as a TRACED SMEM scalar so online vocab growth within the pad
    reuses the compiled program instead of retracing per tick.

    With `q_scale`/`item_scale` set the dot is multiplied by their
    outer product in registers: int8 dequantization and cosine inverse
    norms are the same operation, so every verb (dot recommend, cosine
    similar) and every dtype (f32/bf16/int8) is this one kernel."""
    b, kdim = q.shape
    n_items_p = itf.shape[0]
    int8 = itf.dtype == jnp.int8
    scaled = q_scale is not None
    if int8 and not scaled:
        raise ValueError("int8 factors require dequant scales")
    kind = _mask_kind(mask_bits, exclude_rows)
    return _fused_call(
        b=b, kdim=kdim, n_items_p=n_items_p, k=k, item_tile=item_tile,
        interpret=interpret, precomputed=False, scaled=scaled, int8=int8,
        mask_kind=kind,
        n_excl=0 if exclude_rows is None else exclude_rows.shape[1],
        n_items=n_items,
        main_args=[q, itf],
        main_specs=lambda tile: [
            _bspec((b, kdim), lambda j: (0, 0)),
            _bspec((tile, kdim), lambda j: (j, 0)),
        ],
        scale_args=[q_scale, item_scale],
        mask_arg=mask_bits if kind == "bits" else exclude_rows,
    )


@functools.partial(
    jax.jit,
    static_argnames=("k", "interpret", "item_tile"),
)
def fused_masked_topk(  # lint: disable=jit-boundary — inner boundary:
    # invoked inside cco.batch_score_topk, which is instrumented; this
    # jit inlines into its trace
    scores: jax.Array,  # (B, I_p) f32 — precomputed score matrix
    mask_bits=None,
    exclude_rows=None,
    *,
    k: int,
    n_items,
    interpret: bool = False,
    item_tile: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Fused exclusion + top-k over a PRECOMPUTED score matrix — the
    CCO/universal `batch_score_topk` tail (its scores accumulate by
    gather, so there is no factor matmul to fuse, but the masked-copy
    write + top-k re-read and the (B, I) exclusion-mask
    materialization both disappear: scores stream through once,
    exclusion applies in registers off the packed words / row list)."""
    b, n_items_p = scores.shape
    kind = _mask_kind(mask_bits, exclude_rows)
    return _fused_call(
        b=b, kdim=0, n_items_p=n_items_p, k=k, item_tile=item_tile,
        interpret=interpret, precomputed=True, scaled=False, int8=False,
        mask_kind=kind,
        n_excl=0 if exclude_rows is None else exclude_rows.shape[1],
        n_items=n_items,
        main_args=[scores],
        main_specs=lambda tile: [_bspec((b, tile), lambda j: (0, j))],
        scale_args=[],
        mask_arg=mask_bits if kind == "bits" else exclude_rows,
    )


def _bspec(shape, index_map):
    from jax.experimental import pallas as pl

    return pl.BlockSpec(shape, index_map)


def xla_scores(q, items, qs, isc):
    """The XLA fallback's score semantics, shared by EVERY serving verb
    on every tier: int8 accumulates in int32 and dequantizes by the scale
    product; bf16 accumulates in f32; caller-supplied scales (cosine
    inverse norms) multiply the same way the kernel's register pass
    does. int8 and bf16 scores are the kernel's; f32 scores are too on
    the CPU and, on the TPU, from B = 8 up (module docstring, "On the
    chip": at B = 1 XLA's product is exact and the kernel's is not).

    The f32/bf16 dot is spelled `q @ items.T`, NOT dot_general with a
    (1,)/(1,) contraction: measured on this jax's CPU backend the
    transposed-contraction form picks a GEMM whose last-ulp rounding
    varies with the BATCH size, and the shadow-rollout agreement
    window compares a B=1 mirror against B=n live answers — identical
    models must serialize identical floats regardless of batching
    (regression: tests/test_fused_serving.py batch-size invariance)."""
    if items.dtype == jnp.int8:
        s = jax.lax.dot_general(
            q, items, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
    elif items.dtype == jnp.bfloat16:
        s = jnp.matmul(q, items.T, preferred_element_type=jnp.float32)
    else:
        s = q @ items.T
    if qs is not None:
        s = s * qs * isc
    return s


def fused_or_xla_topk(
    q, items, qs, isc, mask_bits, excl_rows, n_items, *, k, mode
):
    """One dispatch seam for every serving verb on every tier: the
    fused one-pass kernel where a mode resolved, else the XLA two-step
    with IDENTICAL scoring + exclusion semantics (packed words / row
    lists unpack in-jit, so the 1/32 mask-traffic win holds on both
    paths). `n_items` may be traced (the sharded tier passes per-shard
    live counts); dead pad columns sink strictly below NEG_INF."""
    if mode is not None:
        return fused_recommend_topk(
            q, items, qs, isc, mask_bits, excl_rows,
            k=k, n_items=n_items, interpret=(mode == "interpret"),
        )
    s = xla_scores(q, items, qs, isc)
    i_p = int(items.shape[0])
    if mask_bits is not None:
        s = jnp.where(unpack_mask_jnp(mask_bits, i_p), NEG_INF, s)
    elif excl_rows is not None and excl_rows.shape[1]:
        s = jnp.where(rowlist_mask_jnp(excl_rows, i_p), NEG_INF, s)
    col = jnp.arange(i_p, dtype=jnp.int32)
    s = jnp.where(
        (col >= n_items)[None, :], jnp.finfo(jnp.float32).min, s
    )
    return jax.lax.top_k(s, k)


# ---------------------------------------------------------------------------
# int8 quantization (per-row symmetric)
# ---------------------------------------------------------------------------


def quantize_rows_np(arr) -> tuple:
    """Host-side per-row symmetric int8 quantization:
    scale_r = max|row| / 127 (1.0 for all-zero rows so dequant is
    exact zero), q = round(row / scale) in [-127, 127]. Returns
    (int8 (N, K), f32 scales (N,))."""
    import numpy as np

    arr = np.asarray(arr, np.float32)
    amax = np.max(np.abs(arr), axis=1) if arr.size else np.zeros(
        arr.shape[0], np.float32
    )
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(
        np.round(arr / scale[:, None]), -127, 127
    ).astype(np.int8)
    return q, scale


def quantize_rows_jnp(arr: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Traced twin of `quantize_rows_np` for in-jit query-row
    quantization (the gather side of int8 serving)."""
    amax = jnp.max(jnp.abs(arr), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(arr / scale), -127, 127).astype(jnp.int8)
    return q, scale


def inv_norms_np(arr, pad_to: int = 0):
    """Per-row inverse L2 norms 1/(|row|+1e-9) as a (1, N_p) f32 row —
    the cosine verbs' item-side scale, computed ONCE at stage time from
    the f32 factors (pad rows get 0.0: their scores are dead either
    way, and 0 keeps them finite)."""
    import numpy as np

    arr = np.asarray(arr, np.float32)
    n = arr.shape[0]
    out = np.zeros((1, max(pad_to, n)), np.float32)
    # in row chunks: `norm` squares into a temporary the size of its
    # input, 10 GB more of host memory to touch at a 19.7 M-row table
    chunk = 1 << 18
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        out[0, lo:hi] = 1.0 / (np.linalg.norm(arr[lo:hi], axis=1) + 1e-9)
    return out


# ---------------------------------------------------------------------------
# gating
# ---------------------------------------------------------------------------


def resolve_mode(requested: str = "auto"):
    """None (XLA two-step), "tpu", or "interpret" — resolved OUTSIDE
    the jit so trace caches key on it (windowed_pallas precedent).

    Default: ON where the TPU lowering can run (the score-matrix HBM
    round-trip it removes dominates the pass at serving rank), off
    elsewhere. PIO_PALLAS_RECOMMEND=0 forces the XLA path, =interpret
    runs the kernel through the Pallas interpreter (the CPU test
    path)."""
    if requested in (None, "off"):
        return None
    if requested == "interpret":
        return "interpret"
    env = _env_str("PIO_PALLAS_RECOMMEND").strip()
    if env == "0":
        return None
    if env == "interpret":
        return "interpret"
    from predictionio_tpu.utils.jaxenv import on_tpu

    return "tpu" if on_tpu() else None
