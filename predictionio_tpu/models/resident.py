"""`ResidentServing`: the one owner of a model's device-resident serving
state — which tier serves it, how that state is staged, how a fold-in
tick carries it, what it reports and how it is released.

Two tiers hold factor state across queries: one chip's `ServingFactors`
(models/als.py, the `*_serving` verbs) and, with `shard=True` and two or
more visible devices, a `fleet.ShardedRuntime` row-sharded over the
serving mesh. The engines call the three verbs here and keep what is
theirs: vocab look-ups, exclusion semantics, bucket padding, decode.
`online/foldin.py` carries the state through `adopt`; the server's fleet
status and the tenant cache read `sharded_info()` and `device_bytes()`
through the models' one-line hooks; `info()` is either tier's layout.

`shard` is an argument, not a field: the engines read `shard_serving`
from the deployed algorithm's params at predict time. `fleet.runtime`
imports lazily (it builds meshes; models that never shard never load
it).

`ResidentCorrelators` is the same ownership for a co-occurrence model
(the Universal Recommender): its correlator tables staged once, padded
at staging, under the same one-device budget and byte count. It has the
one-chip tier only, and `models/cco.py` loads when it stages — the
factor engines never import it.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

import numpy as np

from predictionio_tpu.models import als

log = logging.getLogger(__name__)


def _device_budget() -> Optional[float]:
    """One device's serving budget in bytes: PIO_SERVE_HBM_BYTES, else
    the memory the device reports (a CPU reports none: no gate)."""
    import jax

    from predictionio_tpu.utils.env import env_opt_float

    budget = env_opt_float("PIO_SERVE_HBM_BYTES")
    if budget is None:
        stats = jax.devices()[0].memory_stats() or {}
        budget = stats.get("bytes_limit")
    return None if budget is None else float(budget)


class ResidentServing:
    """Lazily staged serving state for `factors` (an `als.ALSFactors`,
    or anything with its five fields). `item_only` stages the item side
    alone on one chip (similarproduct: its verbs never read a user row);
    the sharded tier always takes both sides. Pickles as its constructor
    arguments — never the staged state or the lock."""

    def __init__(
        self, factors, serve_dtype: str = "f32", item_only: bool = False
    ):
        self.factors = factors
        self.serve_dtype = serve_dtype
        self.item_only = item_only
        # locked: the pipelined dispatcher runs concurrent batches for
        # one model, and a double staging would transiently double the
        # device footprint
        self._lock = threading.Lock()
        self._single = None  # als.ServingFactors when staged
        self._sharded = None  # fleet.ShardedRuntime when staged
        # the probe's "fewer than two devices" outcome, kept so the hot
        # path never asks jax.devices() again under the lock
        self._one_device = False

    def __reduce__(self):
        return (
            type(self), (self.factors, self.serve_dtype, self.item_only)
        )

    # -- staging -------------------------------------------------------------
    def _runtime(self, shard: bool):
        """The sharded tier where it serves — `shard` asked for and two
        or more devices visible, PIO_SERVE_HBM_BYTES the budget of each
        — staged on first use; else None."""
        if not shard:
            return None
        with self._lock:
            if self._sharded is None and not self._one_device:
                import jax

                if len(jax.devices()) < 2:
                    self._one_device = True
                else:
                    from predictionio_tpu.fleet.runtime import ShardedRuntime
                    from predictionio_tpu.utils.env import env_opt_float

                    self._sharded = ShardedRuntime.from_factors(
                        self.factors,
                        device_budget_bytes=env_opt_float(
                            "PIO_SERVE_HBM_BYTES"
                        ),
                        serve_dtype=self.serve_dtype,
                    )
            return self._sharded

    def is_sharded(self, shard: bool) -> bool:
        """Whether the sharded tier serves under `shard` (stages it if
        so; never stages the one-chip state)."""
        return self._runtime(shard) is not None

    def get(self, shard: bool = False):
        """The staged state of the tier that serves: a `ShardedRuntime`,
        else the one chip's `ServingFactors` — pad-aligned for the fused
        kernel, quantized when serve_dtype opts in, resident across
        calls."""
        srt = self._runtime(shard)
        if srt is not None:
            return srt
        with self._lock:
            if self._single is None:
                self._check_fits_one_device()
                if self.item_only:
                    self._single = als.stage_item_serving(
                        self.factors.item_factors,
                        serve_dtype=self.serve_dtype,
                    )
                else:
                    self._single = als.stage_serving(
                        self.factors, serve_dtype=self.serve_dtype
                    )
            return self._single

    def _check_fits_one_device(self) -> None:
        """`OversizedModelError`, naming the sharded tier, where the
        factor state is over one device's budget — PIO_SERVE_HBM_BYTES,
        else the memory the device reports (a CPU reports none: no
        gate) — instead of a death in the allocator mid-staging."""
        budget = _device_budget()
        if budget is None:
            return
        from predictionio_tpu.fleet.runtime import check_single_device_budget

        uf, itf = self.factors.user_factors, self.factors.item_factors
        check_single_device_budget(
            0 if self.item_only else uf.shape[0],
            itf.shape[0],
            uf.shape[1],
            budget,
            serve_dtype=self.serve_dtype,
        )

    # -- the three verbs, one signature on either tier -----------------------
    def recommend(
        self, rows, k: int, exclude_mask=None, exclude_rows=None,
        *, shard: bool = False,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-k items per user row. Sharded: local top-k per shard +
        global merge, factor state row-sharded in HBM. One chip: the
        fused one-pass kernel where the lowering runs, int8/bf16 when
        the model opts in. Either way the exclusion ships as a row list
        or packed bit words — never an f32 mask."""
        state = self.get(shard)
        if isinstance(state, als.ServingFactors):
            return als.recommend_serving(
                state, rows, k,
                exclude_mask=exclude_mask, exclude_rows=exclude_rows,
            )
        return state.recommend(
            rows, k, exclude_mask=exclude_mask, exclude_rows=exclude_rows
        )

    def similar_items(
        self, rows, k: int, exclude_self: bool = True,
        *, shard: bool = False,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Cosine top-k for a batch of item rows off the resident item
        slab."""
        state = self.get(shard)
        if isinstance(state, als.ServingFactors):
            return als.similar_serving(
                state, rows, k, exclude_self=exclude_self
            )
        return state.similar_items(rows, k, exclude_self=exclude_self)

    def similar_vectors(
        self, vecs, k: int, exclude_mask=None, *, shard: bool = False
    ) -> tuple[np.ndarray, np.ndarray]:
        """Cosine top-k against arbitrary f32 query vectors (a basket
        mean). Sharded: each shard scores its slab, only the (B, k)
        candidates ride the merge."""
        state = self.get(shard)
        if isinstance(state, als.ServingFactors):
            return als.similar_vectors_serving(
                state, vecs, k, exclude_mask=exclude_mask
            )
        return state.similar_vectors(vecs, k, exclude_mask=exclude_mask)

    # -- fold-in carry -------------------------------------------------------
    def adopt(
        self, old: "ResidentServing", dirty_users=None, dirty_items=None
    ) -> None:
        """Fold-in publish (online/foldin.py:_clone_model): carry the
        predecessor's staged state by publishing ONLY the tick's dirty
        rows — `(rows, f32 values)` a side, None for a side that did
        not change — instead of re-staging a factor matrix per tick.
        Any failure leaves the tier unstaged; the next query restages
        from the folded factors."""
        self._one_device = old._one_device
        n_users = self.factors.user_factors.shape[0]
        n_items = self.factors.item_factors.shape[0]
        if old._single is not None:
            self._single = _publish_single(
                old._single, dirty_users, dirty_items, n_users, n_items
            )
        if old._sharded is not None:
            self._sharded = _publish_sharded(
                old._sharded, dirty_users, dirty_items, n_users, n_items
            )

    # -- accounting and release ----------------------------------------------
    def sharded_info(self) -> Optional[dict]:
        """Shard layout for the server's fleet status (None when the
        sharded tier is not staged)."""
        srt = self._sharded
        return srt.info() if srt is not None else None

    def info(self) -> Optional[dict]:
        """What is staged, on the tier that serves: the sharded layout,
        else the one chip's (`shards` 1); None when nothing is staged.
        Either names the item rows staged, pad included, and the kernel
        tile they take — what `pad_items` decided from the catalogue's
        size — so a run can say it without a trace."""
        srt, sv = self._sharded, self._single
        if srt is not None:
            return srt.info()
        if sv is None:
            return None
        return {
            "shards": 1,
            "n_users": sv.n_users,
            "n_items": sv.n_items,
            "serve_dtype": sv.dtype,
            "serve_mode": sv.mode or "xla",
            "item_rows_padded": int(sv.items.shape[0]),
            "item_tile": sv.item_tile,
            "resident_bytes_total": sv.device_nbytes(),
        }

    def device_bytes(self) -> Optional[float]:
        """Per-device bytes of what is staged: one SHARD when serving
        sharded — the point of that tier is that no chip holds the
        catalog — else the one chip's staged (possibly int8) state;
        None when nothing is staged."""
        srt, sv = self._sharded, self._single
        if srt is not None:
            return float(srt.device_bytes()["per_shard"])
        return sv.device_nbytes() if sv is not None else None

    def drop(self) -> None:
        """Release both tiers' staged state: the device buffers go when
        the last in-flight batch lets go of them, and the next query
        restages."""
        with self._lock:
            self._single = self._sharded = None


class ResidentCorrelators:
    """Lazily staged correlator tables of a co-occurrence model:
    `tables` is `[(corr_idx (I, T), corr_scores (I, T), J), …]`, one
    entry an indicator, the host arrays the model keeps. `get()` stages
    them once (`cco.stage_correlators`: inverted on the device into
    postings sorted by thing, as the scoring program reads them) after
    the one-device budget check, as the span `ur.stage`. Never pickled:
    the model rebuilds it from its tables."""

    def __init__(self, tables: list):
        self.tables = tables
        # locked: the pipelined dispatcher runs concurrent batches for
        # one model, and a double staging would transiently double the
        # device footprint
        self._lock = threading.Lock()
        self._staged = None  # cco.StagedCorrelators when staged

    def get(self):
        """The resident `cco.StagedCorrelators`, staged on first use.
        `OversizedModelError`, naming the table set and the three terms
        of its peak (`cco.device_peak_bytes`: the resident postings, the
        staging sort's arrays beside them, the widest bucket's total),
        where that peak is over one device's budget — instead of a
        death in the allocator mid-staging or in the first full batch
        (this model has no sharded tier to name)."""
        from predictionio_tpu.models import cco
        from predictionio_tpu.obs import spans as _spans

        with self._lock:
            if self._staged is None:
                resident, sort, total = cco.device_peak_bytes(self.tables)
                budget = _device_budget()
                if budget is not None and resident + sort + total > budget:
                    from predictionio_tpu.fleet.runtime import (
                        OversizedModelError,
                    )

                    rows = int(np.shape(self.tables[0][0])[0])
                    raise OversizedModelError(
                        f"correlator tables ({len(self.tables)} indicators "
                        f"x {rows} items) need "
                        f"{(resident + sort + total) / 1e9:.2f} GB at "
                        f"their peak — {resident / 1e9:.2f} resident, "
                        f"{sort / 1e9:.2f} for the staging sort of one "
                        f"indicator beside them, {total / 1e9:.2f} for a "
                        f"{cco.MAX_BATCH}-query batch's total — but the "
                        f"single-device budget is {budget / 1e9:.2f} GB; "
                        "co-occurrence serving has no sharded tier"
                    )
                with _spans.span(
                    "ur.stage", indicators=len(self.tables)
                ) as sp:
                    self._staged = cco.stage_correlators(self.tables)
                    sp.attrs["bytes"] = self._staged.nbytes
                    sp.attrs["item_rows_padded"] = self._staged.rows_padded
            return self._staged

    def info(self) -> Optional[dict]:
        """What is staged (None when nothing is): the live and the
        padded item rows and the resident bytes."""
        st = self._staged
        if st is None:
            return None
        return {
            "shards": 1,
            "n_items": st.n_items,
            "indicators": len(st.offsets),
            "item_rows_padded": st.rows_padded,
            "resident_bytes_total": st.nbytes,
        }

    def device_bytes(self) -> Optional[float]:
        st = self._staged
        return float(st.nbytes) if st is not None else None

    def drop(self) -> None:
        """Release the staged tables: the device buffers go when the
        last in-flight batch lets go of them, and the next query
        restages."""
        with self._lock:
            self._staged = None


def _publish_single(old_state, dirty_users, dirty_items, n_users, n_items):
    """Dirty rows into one chip's state, device-side (quantize-at-fold-in
    for int8): copy-on-write off shared buffers, donated into grown
    private ones. Returns the successor state, or None to restage."""
    # a side that changed without row attribution cannot be expressed
    # as row writes — leave unstaged (lazy restage)
    if dirty_users is None and n_users != old_state.n_users:
        return None
    if dirty_items is None and n_items != old_state.n_items:
        return None
    ur, uv = dirty_users or (None, None)
    ir, iv = dirty_items or (None, None)
    try:
        return als.serving_publish_rows(
            old_state,
            user_rows=ur, user_vals=uv,
            item_rows=ir, item_vals=iv,
            n_users=n_users, n_items=n_items,
        )
    except Exception:
        log.exception(
            "dirty-row publish into the staged state failed — dropping "
            "the carry so the next query restages"
        )
        return None


def _publish_sharded(runtime, dirty_users, dirty_items, n_users, n_items):
    """Dirty rows into the RESIDENT sharded slabs through
    `ShardedRuntime.update_*_rows` — re-quantizing just those rows and
    donating the slab once in-flight readers drain. Rows beyond the
    padded shard extent (vocab growth) drop the carry; the next query
    rebuilds lazily (the amortized-growth contract). Returns the runtime,
    or None to restage."""
    # validate BOTH sides BEFORE mutating either: the runtime is shared
    # in place with the still-serving predecessor, so a user-side write
    # followed by an item-side growth refusal would leave the LIVE state
    # half-updated with no rollback
    for side, dirty in (("user", dirty_users), ("item", dirty_items)):
        if dirty is not None and not runtime.rows_within_extent(
            side, dirty[0]
        ):
            return None
    try:
        # within-pad growth must raise the live extent or the grown rows
        # stay masked dead (the one-chip publish's n_users/n_items twin)
        if dirty_users is not None and len(dirty_users[0]):
            runtime.update_user_rows(*dirty_users, n_users=n_users)
        if dirty_items is not None and len(dirty_items[0]):
            runtime.update_item_rows(*dirty_items, n_items=n_items)
        return runtime
    except Exception:
        log.exception(
            "sharded dirty-row publish failed mid-carry; the runtime may "
            "be half-updated — dropping the carry so the next query "
            "restages from the folded factors"
        )
        return None
