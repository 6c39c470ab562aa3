"""Similar-product engine template: item-item similarity on ALS factors.

Reference: examples/scala-parallel-similarproduct (6 variants incl.
multi-algo) — DataSource reads "view" events; ALSAlgorithm trains implicit
ALS and keeps productFeatures; predict averages the query items' vectors
and returns cosine top-N excluding the query items; the `multi` variant
adds LikeAlgorithm (like/dislike events weighted ±1) and combines
predictions in Serving.

TPU re-design: one factor-training program shared with the recommendation
template (models/als.py); similarity serving is a cached-normalized
matmul + shared top-k ranking (models/ranking.py — host path; the
batched device path lives in models/als.similar_items)."""

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
    Serving,
)
from predictionio_tpu.core.base import RuntimeContext
from predictionio_tpu.data.store.event_store import EventStoreFacade
from predictionio_tpu.models import als, ranking
from predictionio_tpu.models.resident import ResidentServing


@dataclass
class Query:
    items: list[str] = field(default_factory=list)
    num: int = 10
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
class DataSourceParams:
    app_name: str
    view_event: str = "view"
    like_event: str = "like"
    dislike_event: str = "dislike"


@dataclass
class TrainingData(SanityCheck):
    # view interactions
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    # like/dislike interactions (±1 weights) for LikeAlgorithm
    like_rows: np.ndarray
    like_cols: np.ndarray
    like_vals: np.ndarray
    n_users: int
    n_items: int
    user_vocab: object
    item_vocab: object

    def sanity_check(self) -> None:
        if len(self.rows) == 0 and len(self.like_rows) == 0:
            raise ValueError("no view or like/dislike events found")


class SimilarProductDataSource(DataSource):
    def __init__(self, params: DataSourceParams):
        self.params = params

    def read_training(self, ctx: RuntimeContext) -> TrainingData:
        store = EventStoreFacade(ctx.storage)
        frame = store.find_frame(
            app_name=self.params.app_name,
            entity_type="user",
            target_entity_type="item",
            event_names=[
                self.params.view_event,
                self.params.like_event,
                self.params.dislike_event,
            ],
        )
        views = frame.where_event(self.params.view_event)
        v_rows, v_cols, v_vals = views.interactions(dedupe="sum")

        likes = frame.where_event(
            self.params.like_event, self.params.dislike_event
        )
        like_code = frame.event_vocab.get(self.params.like_event, -2)
        # like=+1 / dislike=-1, latest event wins (reference LikeAlgorithm
        # keeps the most recent rating per pair)
        signed = np.where(likes.event_code == like_code, 1.0, -1.0).astype(
            np.float32
        )
        import dataclasses as _dc

        likes = _dc.replace(likes, value=signed)
        l_rows, l_cols, l_vals = likes.interactions(dedupe="last")

        return TrainingData(
            rows=v_rows, cols=v_cols, vals=v_vals,
            like_rows=l_rows, like_cols=l_cols, like_vals=l_vals,
            n_users=frame.n_entities, n_items=frame.n_targets,
            user_vocab=frame.entity_vocab, item_vocab=frame.target_vocab,
        )


# -- algorithms -------------------------------------------------------------


@dataclass
class ALSSimilarParams:
    rank: int = 10
    num_iterations: int = 20
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int = 3
    # sharded serving (ISSUE 11 satellite, carried fleet follow-up):
    # with > 1 visible device, serve the basket cosine from
    # row-sharded item factors (fleet.ShardedRuntime.similar_vectors)
    # so the catalog can exceed one chip's HBM — the same wiring the
    # recommendation engine got in PR 10.
    shard_serving: bool = False
    # serving dtype for the basket cosine (ISSUE 14): "int8"/"bf16"
    # stage quantized item factors and run the fused scaled-dot cosine
    # (als.similar_vectors_serving); "f32" keeps the exact host path
    # on CPU and the fused kernel where the TPU lowering runs.
    serve_dtype: str = "f32"


class SimilarModel:
    """Item factors + vocab; normalized factors cached across queries."""

    def __init__(self, factors: als.ALSFactors, serve_dtype: str = "f32"):
        self.factors = factors
        self.serve_dtype = serve_dtype
        self._normed = None
        # both sides to the sharded tier, the item side alone to one
        # chip: the basket cosine never reads a user row
        self.resident = ResidentServing(factors, serve_dtype, item_only=True)

    # the caches are serving state, not part of the pickled model
    def __getstate__(self):
        return {
            "factors": self.factors,
            "serve_dtype": self.serve_dtype,
        }

    def __setstate__(self, state):
        # models pickled before serve_dtype existed must keep loading
        self.__init__(
            state["factors"], state.get("serve_dtype", "f32")
        )

    def with_factors(self, factors: als.ALSFactors, carry=None) -> "SimilarModel":
        """A model around folded factors (online/foldin.py), same serve
        dtype. The tick's dirty rows (`carry`) are not published: the
        one-chip state is item-only, so it restages lazily."""
        return SimilarModel(factors, self.serve_dtype)

    def normed_item_factors(self) -> np.ndarray:
        if self._normed is None:
            self._normed = ranking.l2_normalize(self.factors.item_factors)
        return self._normed

    def sharded_info(self):
        return self.resident.sharded_info()


class _SimilarBase(Algorithm):
    """Shared serving: average query item vectors → cosine top-N."""

    def _exclusion(self, model: SimilarModel, query: Query, known) -> np.ndarray:
        vocab = model.factors.item_vocab
        n = model.factors.item_factors.shape[0]
        excluded = np.zeros(n, dtype=bool)
        excluded[known] = True  # never recommend the query items
        if query.whitelist is not None:
            keep = np.zeros(n, dtype=bool)
            for it in query.whitelist:
                ix = vocab.get(it)
                if ix is not None:
                    keep[ix] = True
            excluded |= ~keep
        for it in query.blacklist or []:
            ix = vocab.get(it)
            if ix is not None:
                excluded[ix] = True
        return excluded

    def _predict(self, model: SimilarModel, query: Query) -> PredictedResult:
        vocab = model.factors.item_vocab
        known = [vocab.get(i) for i in query.items]
        known = [k for k in known if k is not None]
        if not known:
            return PredictedResult()
        excluded = self._exclusion(model, query, known)
        inv = vocab.inverse()
        def basket_result(vals, idx, qnorm):
            # both device routes score the mean of NORMALIZED vectors
            # and divide by the query norm (cosine), so multiply it
            # back — the same query must yield the same SCORES as the
            # host path regardless of route/device count, not just the
            # same ranking (clients threshold on values). Filter masked
            # entries on the RAW value FIRST: a scale < 0.5 would
            # otherwise lift NEG_INF past the filter bound.
            from predictionio_tpu.ops.topk import NEG_INF

            return PredictedResult(
                item_scores=[
                    ItemScore(item=inv(int(ix)), score=float(s * qnorm))
                    for s, ix in zip(vals[0], idx[0])
                    if s > NEG_INF / 2
                ]
            )

        shard = getattr(self.params, "shard_serving", False)
        serve_dtype = getattr(self.params, "serve_dtype", "f32")
        from predictionio_tpu.ops.recommend_pallas import resolve_mode

        if (
            model.resident.is_sharded(shard)
            or serve_dtype != "f32"
            or resolve_mode("auto") is not None
        ):
            # resident basket cosine: the mean query vector scores the
            # quantized resident item factors in one fused score+top-k
            # dispatch (sharded: each shard its slab, only the (1, k)
            # candidates ride the ICI merge); the host path below
            # survives as the exact-f32 CPU default
            q = model.normed_item_factors()[known].mean(axis=0)
            vals, idx = model.resident.similar_vectors(
                q[None, :], query.num, exclude_mask=excluded[None, :],
                shard=shard,
            )
            return basket_result(
                vals, idx, float(np.linalg.norm(q)) + 1e-9
            )
        normed = model.normed_item_factors()
        scores = normed @ normed[known].mean(axis=0)
        scores = ranking.exclusion_scores(scores, excluded)
        return PredictedResult(
            item_scores=[
                ItemScore(item=inv(int(ix)), score=float(scores[ix]))
                for ix in ranking.top_k_indices(scores, query.num)
            ]
        )

    def predict(self, model: SimilarModel, query: Query) -> PredictedResult:
        return self._predict(model, query)


class ALSSimilarAlgorithm(_SimilarBase):
    """Implicit ALS on view events (reference ALSAlgorithm.scala of the
    similarproduct template)."""

    def __init__(self, params: ALSSimilarParams):
        self.params = params

    def train(self, ctx: RuntimeContext, pd: TrainingData) -> SimilarModel:
        factors = als.train(
            pd.rows, pd.cols, pd.vals, pd.n_users, pd.n_items,
            als.ALSParams(
                rank=self.params.rank,
                iterations=self.params.num_iterations,
                lambda_=self.params.lambda_,
                alpha=self.params.alpha,
                implicit_prefs=True,
                seed=self.params.seed,
            ),
            user_vocab=pd.user_vocab,
            item_vocab=pd.item_vocab,
            mesh=ctx.mesh,
        )
        return SimilarModel(
            factors, serve_dtype=getattr(self.params, "serve_dtype", "f32")
        )


class LikeAlgorithm(_SimilarBase):
    """Same factorization over like/dislike ±1 events (reference
    LikeAlgorithm.scala — the multi variant's second algorithm)."""

    def __init__(self, params: ALSSimilarParams):
        self.params = params

    def train(self, ctx: RuntimeContext, pd: TrainingData) -> SimilarModel:
        if len(pd.like_rows) == 0:
            raise ValueError("LikeAlgorithm requires like/dislike events")
        factors = als.train(
            pd.like_rows, pd.like_cols, pd.like_vals, pd.n_users, pd.n_items,
            als.ALSParams(
                rank=self.params.rank,
                iterations=self.params.num_iterations,
                lambda_=self.params.lambda_,
                alpha=self.params.alpha,
                implicit_prefs=True,
                seed=self.params.seed,
            ),
            user_vocab=pd.user_vocab,
            item_vocab=pd.item_vocab,
            mesh=ctx.mesh,
        )
        return SimilarModel(
            factors, serve_dtype=getattr(self.params, "serve_dtype", "f32")
        )


class SumScoreServing(Serving):
    """Multi-algo combination: sum per-item scores across algorithms
    (reference multi variant's Serving.scala)."""

    def serve(
        self, query: Query, predictions: Sequence[PredictedResult]
    ) -> PredictedResult:
        combined: dict[str, float] = {}
        for p in predictions:
            for s in p.item_scores:
                combined[s.item] = combined.get(s.item, 0.0) + s.score
        top = sorted(combined.items(), key=lambda kv: -kv[1])[: query.num]
        return PredictedResult(
            item_scores=[ItemScore(item=i, score=v) for i, v in top]
        )


class SimilarProductEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            SimilarProductDataSource,
            IdentityPreparator,
            {"als": ALSSimilarAlgorithm, "like": LikeAlgorithm},
            {"": FirstServing, "sum": SumScoreServing},
        )
