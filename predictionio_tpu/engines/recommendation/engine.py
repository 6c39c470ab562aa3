"""Recommendation engine template: implicit/explicit ALS → top-N items.

Reference: examples/scala-parallel-recommendation (4 variants — DataSource
reads "rate"/"buy" events, custom-query/src/main/scala/DataSource.scala:24-80;
ALSAlgorithm.scala:50-120 delegates to MLlib ALS, predict = factor
dot-products + top-N; Serving = first).

TPU re-design: the DataSource reads one columnar EventFrame (no RDD), the
algorithm trains with models/als.py's batched-CG XLA program on ctx.mesh,
and the model keeps item factors device-resident so serving is one
matmul+top-k program per query batch.

Eval support mirrors the template's query/actual protocol: hold out each
fold's events per user; Query carries the user, Actual the held-out item
set (rated >= goal threshold).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    DataSource,
    Engine,
    EngineFactory,
    FirstServing,
    IdentityPreparator,
    SanityCheck,
)
from predictionio_tpu.controller.metrics import OptionAverageMetric
from predictionio_tpu.core.base import RuntimeContext
from predictionio_tpu.data.store.event_store import EventStoreFacade
from predictionio_tpu.models import als
from predictionio_tpu.models.resident import ResidentServing
from predictionio_tpu.obs import devprof as _devprof
from predictionio_tpu.obs import spans as _spans


# -- query/result (reference Engine.scala of the template) ------------------


@dataclass
class Query:
    user: str
    num: int = 10
    # filter-by-category variant surface
    categories: Optional[list[str]] = None
    whitelist: Optional[list[str]] = None
    blacklist: Optional[list[str]] = None


@dataclass
class ItemScore:
    item: str
    score: float


@dataclass
class PredictedResult:
    item_scores: list[ItemScore] = field(default_factory=list)


@dataclass
class ActualResult:
    """Held-out relevant items for eval."""

    items: list[str] = field(default_factory=list)


# -- data source ------------------------------------------------------------


@dataclass
class DataSourceParams:
    app_name: str
    event_names: tuple[str, ...] = ("rate", "buy")
    rate_event: str = "rate"  # carries a "rating" property; others weight 1.0
    eval_k: int = 0  # >0 enables read_eval with k folds
    goal_threshold: float = 4.0  # rating >= threshold counts as relevant
    eval_num: int = 20  # top-N requested per eval query (≥ the metric's k)
    # read item $set properties for category filtering (the reference keeps
    # this in a separate filter-by-category variant; off by default so the
    # plain variant pays no extra event-store scan)
    read_item_categories: bool = False
    # cache the folded EventFrame keyed by (query, data version): repeated
    # trainings of an unchanged window skip the event scan+fold entirely
    # (data/view.py; reference DataView.scala:37-110)
    use_data_view: bool = False
    data_view_dir: Optional[str] = None  # default $PIO_FS_BASEDIR/view


@dataclass
class TrainingData(SanityCheck):
    rows: np.ndarray  # user idx per interaction
    cols: np.ndarray  # item idx
    vals: np.ndarray  # rating / implicit weight
    n_users: int
    n_items: int
    user_vocab: object  # BiMap str → int
    item_vocab: object
    # item row → category set, from item $set properties (reference
    # filter-by-category variant reads categories in its DataSource)
    item_categories: Optional[list[frozenset]] = None

    def sanity_check(self) -> None:
        if len(self.rows) == 0:
            raise ValueError(
                "no interaction events found (check appName/eventNames)"
            )
        if not np.isfinite(self.vals).all():
            raise ValueError("non-finite interaction values")


@dataclass
class EvalInfo:
    fold: int


class RecommendationDataSource(DataSource):
    def __init__(self, params: DataSourceParams):
        self.params = params

    def _frame(self, ctx: RuntimeContext):
        frame_kwargs = dict(
            app_name=self.params.app_name,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.event_names),
            value_prop="rating",
            default_value=1.0,
        )
        if self.params.use_data_view:
            from predictionio_tpu.data.view import DataView

            frame = DataView(self.params.data_view_dir).find_frame(
                ctx.storage, **frame_kwargs
            )
        else:
            frame = EventStoreFacade(ctx.storage).find_frame(**frame_kwargs)
        # only the rate event carries a rating payload; every other
        # interaction type ("buy", "view"…) weighs 1.0 even if it happens
        # to have a "rating" property (reference custom-query DataSource
        # maps rate→rating, others→1)
        rate_code = frame.event_vocab.get(self.params.rate_event, -2)
        import dataclasses as _dc

        return _dc.replace(
            frame,
            value=np.where(frame.event_code == rate_code, frame.value, 1.0).astype(
                np.float32
            ),
        )

    def _item_categories(
        self, ctx: RuntimeContext, item_vocab
    ) -> Optional[list[frozenset]]:
        if not self.params.read_item_categories:
            return None
        store = EventStoreFacade(ctx.storage)
        props = store.aggregate_properties(
            app_name=self.params.app_name, entity_type="item"
        )
        if not props:
            return None
        out: list[frozenset] = [frozenset()] * len(item_vocab)
        for item_id, pmap in props.items():
            row = item_vocab.get(item_id)
            if row is not None:
                cats = pmap.get_opt("categories", list) or []
                out[row] = frozenset(cats)
        return out

    def read_training(self, ctx: RuntimeContext) -> TrainingData:
        frame = self._frame(ctx)
        rows, cols, vals = frame.interactions(dedupe="sum")
        return TrainingData(
            rows=rows,
            cols=cols,
            vals=vals,
            n_users=frame.n_entities,
            n_items=frame.n_targets,
            user_vocab=frame.entity_vocab,
            item_vocab=frame.target_vocab,
            item_categories=self._item_categories(ctx, frame.target_vocab),
        )

    def read_eval(self, ctx: RuntimeContext):
        """k-fold split by interaction index (reference e2
        CrossValidation.splitData:21 — fold = idx mod k)."""
        k = self.params.eval_k
        if k <= 0:
            raise ValueError("eval requires datasource params eval_k > 0")
        frame = self._frame(ctx)
        rows, cols, vals = frame.interactions(dedupe="sum")
        idx = np.arange(len(rows))
        inv_user = frame.entity_vocab.inverse()
        inv_item = frame.target_vocab.inverse()
        out = []
        for fold in range(k):
            test_mask = idx % k == fold
            td = TrainingData(
                rows=rows[~test_mask],
                cols=cols[~test_mask],
                vals=vals[~test_mask],
                n_users=frame.n_entities,
                n_items=frame.n_targets,
                user_vocab=frame.entity_vocab,
                item_vocab=frame.target_vocab,
            )
            qa = []
            t_rows, t_cols, t_vals = (
                rows[test_mask], cols[test_mask], vals[test_mask],
            )
            for u in np.unique(t_rows):
                m = (t_rows == u) & (t_vals >= self.params.goal_threshold)
                relevant = [inv_item(int(c)) for c in t_cols[m]]
                if relevant:
                    qa.append(
                        (
                            Query(
                                user=inv_user(int(u)),
                                num=self.params.eval_num,
                            ),
                            ActualResult(relevant),
                        )
                    )
            out.append((td, EvalInfo(fold=fold), qa))
        return out


# -- algorithm --------------------------------------------------------------


@dataclass
class ALSAlgorithmParams:
    rank: int = 10
    num_iterations: int = 20
    lambda_: float = 0.01
    alpha: float = 1.0
    implicit_prefs: bool = True
    cg_iterations: int = 3
    seed: int = 3
    # > 0: snapshot factor state into MODELDATA every N iterations so an
    # interrupted train resumes (workflow/checkpoint.py); 0 disables
    checkpoint_every: int = 0
    # warm-start retrains from the variant's LIVE registry version
    # (ISSUE 9): the parent's factors are mapped onto the new vocab so a
    # periodic retrain reconverges WITH the online fold-in stream
    # instead of re-deriving everything from random init
    warm_start: bool = False
    # sharded serving (ISSUE 10): with > 1 visible device, keep factor
    # state row-sharded across a serving mesh (fleet.ShardedRuntime) so
    # the catalog can exceed one chip's HBM; recommend lowers as local
    # top-k per shard + global merge. Off by default — single-chip
    # serving keeps the PR-2 resident-matrix path.
    shard_serving: bool = False
    # serving dtype (ISSUE 11/14): "int8" quantizes BOTH factor
    # matrices per-row at model publish/fold-in (~1/3 the resident
    # bytes and factor stream; int8xint8->int32 scoring, scale-product
    # dequant in registers); "bf16" (ISSUE 14) is the middle ground —
    # half the bytes, bf16xbf16->f32 scoring. Scores shift by the
    # quantization/rounding error (~1% relative for int8 at serving
    # rank — see tests/test_recommend_pallas.py bounds), so both are
    # explicit opt-ins; "f32" keeps f32 storage and accumulation (on the
    # TPU the MXU still rounds the dot's operands to bf16: scores within
    # 2^-8·Σ|u_k·x_k| of exact — ops/recommend_pallas.py). Applies to the
    # single-device staged state AND the sharded tier (ISSUE 14
    # brought ShardedRuntime to dtype parity).
    serve_dtype: str = "f32"


class ALSModel:
    """Trained factors + device-resident factor matrices for serving
    (reference template ALSModel.scala persists factor RDDs; here the
    serving-side copies live in HBM across queries)."""

    def __init__(
        self,
        factors: als.ALSFactors,
        item_categories: Optional[list[frozenset]] = None,
        serve_dtype: str = "f32",
    ):
        self.factors = factors
        self.item_categories = item_categories
        self.serve_dtype = serve_dtype
        self.resident = ResidentServing(factors, serve_dtype)

    # the resident serving state is rebuilt on load, never pickled
    def __getstate__(self):
        return {
            "factors": self.factors,
            "item_categories": self.item_categories,
            "serve_dtype": self.serve_dtype,
        }

    def __setstate__(self, state):
        self.__init__(
            state["factors"],
            state.get("item_categories"),
            state.get("serve_dtype", "f32"),
        )

    def with_factors(self, factors: als.ALSFactors, carry=None) -> "ALSModel":
        """A model around folded factors (online/foldin.py): the same
        serve dtype — an int8 tenant's fold tick must not silently
        republish as f32 — and the categories padded out to a grown
        catalog. `carry` is the tick's `(dirty_users, dirty_items)`,
        each `(rows, values)` or None: the staged serving state adopts
        them; None restages lazily."""
        cats = self.item_categories
        n_items = factors.item_factors.shape[0]
        if cats is not None and len(cats) < n_items:
            cats = list(cats) + [frozenset()] * (n_items - len(cats))
        new = ALSModel(factors, cats, self.serve_dtype)
        if carry is not None:
            new.resident.adopt(self.resident, *carry)
        return new

    # benchmarks/serving.py:283 frees the staged slabs before its
    # reference takes the chip by assigning None here; write-only, and
    # whatever is assigned, the state is dropped (ROADMAP: a benchmark
    # PR should call `resident.drop()`)
    _serving_state = property(
        fset=lambda self, _value: self.resident.drop()
    )

    def sharded_info(self) -> Optional[dict]:
        return self.resident.sharded_info()

    def resident_device_bytes(self) -> float:
        """Per-device HBM footprint for the tenant cache's budget
        (tenancy/cache.py walks to this hook): what is staged, else the
        factor matrices once (the staged device copies mirror the host
        arrays 1:1, so counting the host mirrors AND the copies would
        double-charge)."""
        staged = self.resident.device_bytes()
        if staged is not None:
            return staged
        return float(
            self.factors.user_factors.nbytes
            + self.factors.item_factors.nbytes
        )


class ALSAlgorithm(Algorithm):
    def __init__(self, params: ALSAlgorithmParams):
        self.params = params

    def train(self, ctx: RuntimeContext, pd: TrainingData) -> ALSModel:
        from predictionio_tpu.workflow.checkpoint import (
            CheckpointManager,
            train_als_checkpointed,
        )

        als_params = als.ALSParams(
            rank=self.params.rank,
            iterations=self.params.num_iterations,
            lambda_=self.params.lambda_,
            alpha=self.params.alpha,
            implicit_prefs=self.params.implicit_prefs,
            cg_iterations=self.params.cg_iterations,
            seed=self.params.seed,
        )
        manager = None
        if (
            self.params.checkpoint_every > 0
            and ctx.storage is not None
            and ctx.instance_id
        ):
            manager = CheckpointManager(ctx.storage, ctx.instance_id)
        factors = train_als_checkpointed(
            pd.rows,
            pd.cols,
            pd.vals,
            pd.n_users,
            pd.n_items,
            als_params,
            manager,
            self.params.checkpoint_every,
            user_vocab=pd.user_vocab,
            item_vocab=pd.item_vocab,
            mesh=ctx.mesh,
            init_factors=self._warm_start_init(ctx, pd, als_params),
        )
        return ALSModel(
            factors,
            item_categories=pd.item_categories,
            serve_dtype=getattr(self.params, "serve_dtype", "f32"),
        )

    def _warm_start_init(self, ctx: RuntimeContext, pd: TrainingData,
                         als_params: als.ALSParams):
        """Parent-version factors mapped onto the new vocab (ISSUE 9):
        resolved through the registry lineage — the variant's live
        version is exactly the `parent_version` this train's new record
        will point at. Best-effort: any failure falls back to the cold
        random init."""
        if not self.params.warm_start or ctx.storage is None:
            return None
        try:
            if not ctx.instance_id:
                return None
            inst = ctx.storage.get_meta_data_engine_instances().get(
                ctx.instance_id
            )
            if inst is None:
                return None
            from predictionio_tpu.deploy.registry import ModelRegistry

            live = ModelRegistry(ctx.storage).live_version(
                inst.engine_id, inst.engine_variant
            )
            if live is None:
                return None
            blob = ctx.storage.get_model_data_models().get(live.instance_id)
            if blob is None:
                return None
            from predictionio_tpu.controller.persistent import (
                deserialize_models,
            )

            parent = next(
                (
                    m.factors for m in deserialize_models(blob.models)
                    if hasattr(m, "factors")
                ),
                None,
            )
            if parent is None or parent.params.rank != als_params.rank:
                return None
            import logging as _logging

            _logging.getLogger(__name__).info(
                "warm-starting train from live version %s", live.id
            )
            return als.warm_start_factors(
                parent, pd.user_vocab, pd.item_vocab, als_params
            )
        except Exception:
            import logging as _logging

            _logging.getLogger(__name__).warning(
                "warm start unavailable; using cold init", exc_info=True
            )
            return None

    def train_grid(
        self, ctx: RuntimeContext, pd: TrainingData, params_list
    ) -> list[ALSModel]:
        """A tuning grid trained as batched device programs sharing ONE
        staging (Engine.batch_eval's grid-batched path; VERDICT r3 #6).
        λ/α batch within a launch; rank/iterations/… group into
        per-shape launches over the same staged data (VERDICT r4 #7).
        The serial fallback only remains for eligibility edge cases."""
        als_list = [
            als.ALSParams(
                rank=p.rank,
                iterations=p.num_iterations,
                lambda_=p.lambda_,
                alpha=p.alpha,
                implicit_prefs=p.implicit_prefs,
                cg_iterations=p.cg_iterations,
                seed=p.seed,
            )
            for p in params_list
        ]
        try:
            grid = als.train_grid(
                pd.rows, pd.cols, pd.vals, pd.n_users, pd.n_items,
                als_list, user_vocab=pd.user_vocab, item_vocab=pd.item_vocab,
            )
        except ValueError:  # heterogeneous statics: train serially
            grid = [
                als.train(
                    pd.rows, pd.cols, pd.vals, pd.n_users, pd.n_items, p,
                    user_vocab=pd.user_vocab, item_vocab=pd.item_vocab,
                )
                for p in als_list
            ]
        return [
            ALSModel(
                f,
                item_categories=pd.item_categories,
                serve_dtype=getattr(self.params, "serve_dtype", "f32"),
            )
            for f in grid
        ]

    # -- serving -----------------------------------------------------------
    def warmup(self, model: ALSModel) -> None:
        """Pre-compile the serving programs + stage factors into HBM so the
        first live queries don't pay XLA compile (deploy server calls this
        at build_runtime; reference has no analogue — JVM serving had no
        compile step). Warms the single-query and micro-batch bucket
        shapes."""
        if model.factors.user_factors.shape[0] == 0:
            return
        user = next(iter(model.factors.user_vocab), None)
        if user is None:
            return
        # one known item id (none for an empty catalog: no filter then)
        item = next(iter(model.factors.item_vocab), None)
        item_ids = [] if item is None else [item]
        for batch in (1, 8, 64):  # the full serving bucket ladder
            # nomask program
            self._predict_batch(
                model, [Query(user=user, num=10)] * batch
            )
            # exclusion programs — one per wire form, each its own
            # compiled signature: a known-id blacklist ships as a row
            # list (an unknown id would drop out and warm nothing); a
            # whitelist (like a category filter or a blacklist over
            # ROWLIST_MAX ids) ships as packed bit words
            for filt in ({"blacklist": item_ids}, {"whitelist": item_ids}):
                self._predict_batch(
                    model,
                    [Query(user=user, num=10, **filt)] * batch,
                )

    def _exclusion_mask(
        self, model: ALSModel, queries: Sequence[Query]
    ) -> Optional[np.ndarray]:
        """Category/white/black-list filters → per-query item mask
        (True = exclude)."""
        if not any(q.whitelist or q.blacklist or q.categories for q in queries):
            return None
        vocab = model.factors.item_vocab
        n_items = model.factors.item_factors.shape[0]
        mask = np.zeros((len(queries), n_items), dtype=bool)
        for qi, q in enumerate(queries):
            # three independent exclusions, OR-ed (an item must pass ALL
            # configured filters, matching the reference variant semantics)
            if q.categories:
                if model.item_categories is None:
                    raise ValueError(
                        "query filters by categories but no item category "
                        "properties were found at train time"
                    )
                wanted = set(q.categories)
                no_overlap = np.fromiter(
                    (not (cats & wanted) for cats in model.item_categories),
                    dtype=bool,
                    count=n_items,
                )
                mask[qi] |= no_overlap
            if q.whitelist is not None:
                not_listed = np.ones(n_items, dtype=bool)
                for it in q.whitelist:
                    ix = vocab.get(it)
                    if ix is not None:
                        not_listed[ix] = False
                mask[qi] |= not_listed
            if q.blacklist:
                for it in q.blacklist:
                    ix = vocab.get(it)
                    if ix is not None:
                        mask[qi, ix] = True
        return mask

    def _exclusion_args(
        self, model: ALSModel, queries: Sequence[Query]
    ) -> tuple[Optional[np.ndarray], Optional[np.ndarray]]:
        """(dense mask, row list) — exactly one is set when any filter
        applies. The common small-blacklist case ships a (B, E) int32
        ROW LIST (ISSUE 14): a handful of ids per query instead of an
        n_items-wide mask — the serving layer feeds it straight to the
        fused kernel's row-list input (or bit-packs it at 1/32 the f32
        bytes). Category/whitelist filters — which invert to most-of-
        the-catalog exclusions — keep the dense mask, packed downstream."""
        from predictionio_tpu.ops.recommend_pallas import (
            ROWLIST_MAX,
            rowlist_np,
        )

        if not any(
            q.whitelist or q.blacklist or q.categories for q in queries
        ):
            return None, None
        if any(q.whitelist is not None or q.categories for q in queries):
            return self._exclusion_mask(model, queries), None
        vocab = model.factors.item_vocab
        lists: list[list[int]] = []
        for q in queries:
            rows = [
                ix for it in (q.blacklist or [])
                if (ix := vocab.get(it)) is not None
            ]
            lists.append(rows)
        if max(len(r) for r in lists) > ROWLIST_MAX:
            return self._exclusion_mask(model, queries), None
        # the shared row-list wire convention (pow2 width, -1 pad)
        # lives in ops/recommend_pallas.py — one owner, no drift
        return None, rowlist_np(lists)

    def _predict_batch(
        self, model: ALSModel, queries: Sequence[Query]
    ) -> list[PredictedResult]:
        from predictionio_tpu.ops.topk import NEG_INF
        from predictionio_tpu.utils.bucket import batch_bucket, topk_bucket

        results: list[PredictedResult] = [PredictedResult() for _ in queries]
        # the three host/device phases of a batch are spans (ISSUE 25):
        # what the host does before the device pass, the pass until the
        # answers are host arrays, and the decode back to item ids
        with _spans.span("als.predict.prepare") as sp:
            vocab = model.factors.user_vocab
            known = [(i, vocab.get(q.user)) for i, q in enumerate(queries)]
            known_ix = [(i, u) for i, u in known if u is not None]
            sp.attrs["live"] = len(known_ix)
            if not known_ix:
                return results
            # fixed device-side k (pow2-bucketed above a floor) so q.num
            # does NOT create a new compiled program per distinct value —
            # warmup can actually cover live traffic; results are sliced
            # to num on host
            n_items = model.factors.item_factors.shape[0]
            k_req = min(max(q.num for q in queries), n_items)
            k = topk_bucket(k_req, n_items)
            user_rows = np.array([u for _, u in known_ix], dtype=np.int64)
            full_mask, full_rows = self._exclusion_args(model, queries)
            keep = [i for i, _ in known_ix]
            sub_mask = full_mask[keep] if full_mask is not None else None
            sub_rows = full_rows[keep] if full_rows is not None else None
            n_real = len(user_rows)
            bucket = batch_bucket(n_real)
            sp.attrs["bucket"] = bucket
            if bucket != n_real:
                user_rows = np.concatenate(
                    [user_rows, np.zeros(bucket - n_real, dtype=np.int64)]
                )
                if sub_mask is not None:
                    sub_mask = np.concatenate([
                        sub_mask,
                        np.zeros((bucket - n_real, sub_mask.shape[1]), bool),
                    ])
                if sub_rows is not None:
                    sub_rows = np.concatenate([
                        sub_rows,
                        np.full(
                            (bucket - n_real, sub_rows.shape[1]), -1, np.int32
                        ),
                    ])
        with _spans.span("als.predict.device"):
            # padding-waste accounting (ISSUE 3) lives HERE, at the pad
            # site: this is the only place that knows both the live row
            # count (vocab-known users, not the micro-batch's group size)
            # and the bucket the device program actually ran at
            prof0 = _devprof.snapshot()
            scores, items = model.resident.recommend(
                user_rows, k, exclude_mask=sub_mask, exclude_rows=sub_rows,
                shard=getattr(self.params, "shard_serving", False),
            )
            _devprof.record_batch_padding(
                n_real, bucket,
                flops=_devprof.snapshot().flops - prof0.flops,
            )
            scores = np.asarray(scores)[:n_real]
            items = np.asarray(items)[:n_real]
        with _spans.span("als.predict.decode"):
            inv = model.factors.item_vocab.inverse()
            for row, (qi, _u) in enumerate(known_ix):
                n = min(queries[qi].num, k)
                item_scores = [
                    ItemScore(item=inv(int(ix)), score=float(s))
                    for s, ix in zip(scores[row][:n], items[row][:n])
                    if s > NEG_INF / 2
                ]
                results[qi] = PredictedResult(item_scores=item_scores)
        return results

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        return self._predict_batch(model, [query])[0]

    def batch_predict(self, ctx, model: ALSModel, queries):
        preds = self._predict_batch(model, [q for _, q in queries])
        return [(qx, p) for (qx, _q), p in zip(queries, preds)]


# -- evaluation -------------------------------------------------------------


class PrecisionAtK(OptionAverageMetric):
    """|top-k ∩ relevant| / k, averaged over users with relevant items
    (the standard tuning metric for the recommendation template)."""

    def __init__(self, k: int = 10):
        self.k = k

    def header(self) -> str:
        return f"Precision@{self.k}"

    def calculate_one(self, q: Query, p: PredictedResult, a: ActualResult):
        if not a.items:
            return None
        top = {s.item for s in p.item_scores[: self.k]}
        return len(top & set(a.items)) / self.k


# -- engine factory ---------------------------------------------------------


class RecommendationEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            RecommendationDataSource,
            IdentityPreparator,
            {"als": ALSAlgorithm},
            FirstServing,
        )


# ---------------------------------------------------------------------------
# Custom (foreign-store) data source — the DataSource SPI demo
# ---------------------------------------------------------------------------


@dataclass
class FileDataSourceParams:
    filepath: str
    delimiter: str = "::"  # MovieLens ratings.dat convention


class FileRatingsDataSource(DataSource):
    """The DataSource SPI against a FOREIGN store: `user::item::rating`
    lines from a delimited text file, no event store involved.

    Reference: examples/experimental/
    scala-parallel-recommendation-custom-datasource/DataSource.scala:24-33
    (sc.textFile + split, swapped into the stock recommendation engine) —
    the demo that the DASE contract only requires `read_training`, not
    the framework's own storage. The mongo-datasource experimental demo
    plays the same role against MongoDB; any `read_training` returning
    TrainingData slots into the engine identically."""

    def __init__(self, params: FileDataSourceParams):
        self.params = params

    def read_training(self, ctx: RuntimeContext) -> TrainingData:
        from predictionio_tpu.data.store.bimap import BiMap

        users: dict[str, int] = {}
        items: dict[str, int] = {}
        rows, cols, vals = [], [], []
        with open(self.params.filepath) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                parts = line.split(self.params.delimiter)
                if len(parts) < 3:
                    raise ValueError(
                        f"bad ratings line (want user{self.params.delimiter}"
                        f"item{self.params.delimiter}rating): {line!r}"
                    )
                u, i, r = parts[0], parts[1], float(parts[2])
                rows.append(users.setdefault(u, len(users)))
                cols.append(items.setdefault(i, len(items)))
                vals.append(r)
        return TrainingData(
            rows=np.asarray(rows, np.int32),
            cols=np.asarray(cols, np.int32),
            vals=np.asarray(vals, np.float32),
            n_users=len(users),
            n_items=len(items),
            user_vocab=BiMap(users),
            item_vocab=BiMap(items),
        )


class FileRecommendationEngine(EngineFactory):
    """The stock recommendation engine with the file-backed DataSource
    swapped in — everything downstream (ALS, serving, deploy) unchanged."""

    def apply(self) -> Engine:
        return Engine(
            FileRatingsDataSource,
            IdentityPreparator,
            {"als": ALSAlgorithm},
            FirstServing,
        )
