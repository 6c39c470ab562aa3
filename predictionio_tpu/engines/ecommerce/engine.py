"""E-commerce recommendation template: ALS + serving-time business rules.

Reference: examples/scala-parallel-ecommercerecommendation
(train-with-rate-event, weighted-items variants) — ALS via P2LAlgorithm
with local factor maps; the serving path reads the event store LIVE:
`unseenOnly`/`seenEvents` filters out items the user already interacted
with, an "unavailableItems" constraint entity blocks out-of-stock items,
plus white/black lists (train-with-rate-event/src/main/scala/
ALSAlgorithm.scala:153-221). Unknown users fall back to recently-viewed
items' similarity (predictKnownUser vs predictSimilar paths).

TPU re-design: factors train on device (models/als.py); business-rule
masks are tiny host vectors folded into the masked top-k program."""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Optional

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
from predictionio_tpu.core.base import RuntimeContext
from predictionio_tpu.data.store.event_store import EventStoreFacade
from predictionio_tpu.models import als, ranking

log = logging.getLogger(__name__)


@dataclass
class Query:
    user: str
    num: int = 10
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
class DataSourceParams:
    app_name: str
    # events used as training interactions, with per-event weights; the
    # rate event uses its "rating" property as weight (the
    # train-with-rate-event variant)
    event_names: tuple[str, ...] = ("view", "buy", "rate")
    rate_event: str = "rate"


@dataclass
class TrainingData(SanityCheck):
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    n_users: int
    n_items: int
    user_vocab: object
    item_vocab: object
    item_categories: Optional[list[frozenset]] = None

    def sanity_check(self) -> None:
        if len(self.rows) == 0:
            raise ValueError("no interaction events found")


class ECommerceDataSource(DataSource):
    def __init__(self, params: DataSourceParams):
        self.params = params

    def read_training(self, ctx: RuntimeContext) -> TrainingData:
        store = EventStoreFacade(ctx.storage)
        frame = store.find_frame(
            app_name=self.params.app_name,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.event_names),
            value_prop="rating",
            default_value=1.0,
        )
        import dataclasses as _dc

        rate_code = frame.event_vocab.get(self.params.rate_event, -2)
        frame = _dc.replace(
            frame,
            value=np.where(
                frame.event_code == rate_code, frame.value, 1.0
            ).astype(np.float32),
        )
        rows, cols, vals = frame.interactions(dedupe="sum")
        # item categories from $set properties for category filtering
        props = store.aggregate_properties(
            app_name=self.params.app_name, entity_type="item"
        )
        cats: Optional[list[frozenset]] = None
        if props:
            cats = [frozenset()] * frame.n_targets
            for item_id, pmap in props.items():
                row = frame.target_vocab.get(item_id)
                if row is not None:
                    cats[row] = frozenset(pmap.get_opt("categories", list) or [])
        return TrainingData(
            rows=rows, cols=cols, vals=vals,
            n_users=frame.n_entities, n_items=frame.n_targets,
            user_vocab=frame.entity_vocab, item_vocab=frame.target_vocab,
            item_categories=cats,
        )


# -- algorithm --------------------------------------------------------------


@dataclass
class ECommAlgorithmParams:
    app_name: str
    unseen_only: bool = False
    seen_events: tuple[str, ...] = ("view", "buy")
    similar_events: tuple[str, ...] = ("view",)  # unknown-user fallback basis
    rank: int = 10
    num_iterations: int = 20
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int = 3


class ECommModel:
    def __init__(
        self,
        factors: als.ALSFactors,
        item_categories: Optional[list[frozenset]],
    ):
        self.factors = factors
        self.item_categories = item_categories
        self._normed = None

    def __getstate__(self):
        return {"factors": self.factors, "item_categories": self.item_categories}

    def __setstate__(self, state):
        self.factors = state["factors"]
        self.item_categories = state["item_categories"]
        self._normed = None

    def with_factors(self, factors: als.ALSFactors, carry=None) -> "ECommModel":
        """A model around folded factors (online/foldin.py), categories
        padded out to a grown catalog; no staged state to carry."""
        cats = self.item_categories
        n_items = factors.item_factors.shape[0]
        if cats is not None and len(cats) < n_items:
            cats = list(cats) + [frozenset()] * (n_items - len(cats))
        return ECommModel(factors, cats)

    def normed_item_factors(self) -> np.ndarray:
        if self._normed is None:
            self._normed = ranking.l2_normalize(self.factors.item_factors)
        return self._normed

    def category_index(self) -> dict:
        """category → sorted item-index array, built once per deploy —
        query-time category filtering is then a sparse candidate union
        instead of an O(I) per-query scan."""
        cached = getattr(self, "_cat_index", None)
        if cached is None:
            cached = {}
            for ix, cats in enumerate(self.item_categories or []):
                for c in cats:
                    cached.setdefault(c, []).append(ix)
            cached = {
                c: np.asarray(v, dtype=np.int64) for c, v in cached.items()
            }
            self._cat_index = cached
        return cached


class ECommAlgorithm(Algorithm):
    def __init__(self, params: ECommAlgorithmParams):
        self.params = params

    def train(self, ctx: RuntimeContext, pd: TrainingData) -> ECommModel:
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
        return ECommModel(factors, pd.item_categories)

    # -- serving-time event-store reads (reference ALSAlgorithm.scala:153) --
    def _seen_items(self, ctx: RuntimeContext, user: str) -> set[str]:
        store = EventStoreFacade(ctx.storage)
        try:
            events = store.find_by_entity(
                app_name=self.params.app_name,
                entity_type="user",
                entity_id=user,
                event_names=list(self.params.seen_events),
                target_entity_type="item",
                limit=None,
            )
            return {
                e.target_entity_id for e in events if e.target_entity_id
            }
        except Exception:
            log.exception("seen-items lookup failed; serving unfiltered")
            return set()

    def _unavailable_items(self, ctx: RuntimeContext) -> set[str]:
        """Constraint entity: $set of "items" on
        (entityType=constraint, entityId=unavailableItems) — reference
        ALSAlgorithm.scala reads the latest constraint at query time."""
        store = EventStoreFacade(ctx.storage)
        try:
            app_id, _ = store.app_name_to_id(self.params.app_name)
            pmap = ctx.storage.get_events().aggregate_properties_of_entity(
                app_id, "constraint", "unavailableItems"
            )
            if pmap is None:
                return set()
            return set(pmap.get_opt("items", list) or [])
        except Exception:
            log.exception("unavailable-items lookup failed; ignoring")
            return set()

    def _recent_item_rows(self, ctx: RuntimeContext, user: str, model) -> list[int]:
        """Unknown-user basis: their recent `similar_events` items
        (reference predictSimilar path)."""
        store = EventStoreFacade(ctx.storage)
        try:
            events = store.find_by_entity(
                app_name=self.params.app_name,
                entity_type="user",
                entity_id=user,
                event_names=list(self.params.similar_events),
                target_entity_type="item",
                limit=10,
                latest=True,
            )
            vocab = model.factors.item_vocab
            rows = []
            for e in events:
                ix = vocab.get(e.target_entity_id)
                if ix is not None:
                    rows.append(ix)
            return rows
        except Exception:
            return []

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        # live event-store filters use the injected serving context (the
        # deploy server sets it at build_runtime; tests set it directly)
        return self.predict_with_ctx(self.serving_context, model, query)

    def batch_predict(self, ctx: RuntimeContext, model: ECommModel, queries):
        # eval path: use the eval workflow's ctx so live-store filters are
        # measured the same way the deploy server applies them
        return [
            (qx, self.predict_with_ctx(ctx, model, q)) for qx, q in queries
        ]

    def predict_with_ctx(
        self, ctx: RuntimeContext, model: ECommModel, query: Query
    ) -> PredictedResult:
        vocab = model.factors.item_vocab

        # sparse business-rule filters: a candidate whitelist (categories /
        # explicit whitelist → index arrays) + an exclusion set (blacklist,
        # unavailable, seen, basis). Per-query memory stays
        # O(k + history + filters); no dense item-space mask is built.
        include = None
        if query.categories:
            if model.item_categories is None:
                # fail loudly instead of silently serving every category
                # (same contract as the recommendation template)
                raise ValueError(
                    "query filters by categories but no item category "
                    "properties were found at train time"
                )
            cat_index = model.category_index()
            arrs = [
                cat_index[c] for c in query.categories if c in cat_index
            ]
            include = (
                np.unique(np.concatenate(arrs))
                if arrs
                else np.empty(0, np.int64)
            )
        if query.whitelist is not None:
            wl = np.asarray(
                [
                    ix
                    for it in query.whitelist
                    if (ix := vocab.get(it)) is not None
                ],
                dtype=np.int64,
            )
            include = (
                wl if include is None
                else np.intersect1d(include, wl)
            )
        exclude: list[int] = []
        for it in query.blacklist or []:
            ix = vocab.get(it)
            if ix is not None:
                exclude.append(ix)
        if ctx.storage is not None:
            for it in self._unavailable_items(ctx):
                ix = vocab.get(it)
                if ix is not None:
                    exclude.append(ix)
            if self.params.unseen_only:
                for it in self._seen_items(ctx, query.user):
                    ix = vocab.get(it)
                    if ix is not None:
                        exclude.append(ix)

        user_row = model.factors.user_vocab.get(query.user)
        if user_row is not None:
            scores = model.factors.item_factors @ model.factors.user_factors[
                user_row
            ]
        else:
            # unknown user → similarity to recently-viewed items
            basis = (
                self._recent_item_rows(ctx, query.user, model)
                if ctx.storage is not None
                else []
            )
            if not basis:
                return PredictedResult()
            normed = model.normed_item_factors()
            scores = normed @ normed[basis].mean(axis=0)
            exclude.extend(basis)  # don't recommend the basis items

        inv = vocab.inverse()
        return PredictedResult(
            item_scores=[
                ItemScore(item=inv(int(ix)), score=float(scores[ix]))
                for ix in ranking.top_k_filtered(
                    scores, query.num,
                    exclude_idx=exclude, include_idx=include,
                )
            ]
        )


class ECommServing(FirstServing):
    pass


class ECommerceEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            ECommerceDataSource,
            IdentityPreparator,
            {"ecomm": ECommAlgorithm},
            ECommServing,
        )
