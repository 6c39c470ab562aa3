"""Universal-Recommender-style engine: multi-event CCO + realtime history.

Reference: the ActionML Universal Recommender (external template
actionml/template-scala-parallel-universal-recommendation — the fork's
north-star workload, RELEASE.md:3). Its
prerequisites in the fork are all present here: batch events API,
SelfCleaningDataSource (core/self_cleaning.py), and
deploy-without-retraining.

Shape of the engine:
- DataSource reads one EventFrame per *indicator* event type (the first
  indicator is the PRIMARY — its targets define the recommendation item
  space) and optionally self-cleans the event store first.
- Algorithm computes, per indicator, each item's top correlators by CCO+LLR
  (models/cco.py — Mahout's downsampling, then a join of sorted sparse
  pairs on the device).
- Serving reads the user's RECENT event history live from the event store
  (the reason the reference fork needed serving-time LEventStore reads) and
  scores items by summed LLR over history hits, minus business rules.
"""

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
from predictionio_tpu.core.self_cleaning import EventWindow, SelfCleaningDataSource
from predictionio_tpu.data.store.bimap import BiMap
from predictionio_tpu.data.store.event_store import EventStoreFacade
from predictionio_tpu.models import cco
from predictionio_tpu.models.resident import ResidentCorrelators
from predictionio_tpu.obs import devprof as _devprof
from predictionio_tpu.obs import spans as _spans
from predictionio_tpu.obs.registry import get_default_registry

log = logging.getLogger(__name__)

# what the serving path counts, in the process-wide registry (the engine
# is owned by no one server): batches by the exclusion's WIRE form
# ("none"; "rows" = ids, at most ROWLIST_MAX a query; "mask" = packed
# words, a wider list), the exclusion bytes shipped in either form, and
# the history reads the event store failed
_BATCHES = get_default_registry().counter(
    "ur_batches_total",
    "batches through cco.batch_score_topk, by exclusion wire form",
    labelnames=("form",),  # label-bound: literal none|rows|mask
)
_EXCLUSION_BYTES = get_default_registry().counter(
    "ur_exclusion_bytes_total",
    "bytes of exclusion (row lists or packed words) shipped",
)
_HISTORY_READ_FAILURES = get_default_registry().counter(
    "ur_history_read_failures_total",
    "serving-time history reads the event store failed (served as empty)",
)
# what a train job counts, by indicator (the engine.json's event names):
# the distinct events the downsampling kept, and the primary x indicator
# pairs the join expanded
_EVENTS_KEPT = get_default_registry().counter(
    "ur_train_events_kept_total",
    "distinct events kept by the downsampling, by indicator",
    labelnames=("indicator",),  # label-bound: the engine's event names
)
_PAIRS = get_default_registry().counter(
    "ur_train_pairs_total",
    "primary x indicator pairs the cross-occurrence join expanded",
    labelnames=("indicator",),  # label-bound: the engine's event names
)


@dataclass
class Query:
    user: str
    num: int = 10
    blacklist: Optional[list[str]] = None
    # exclude items the user has already acted on with the primary event
    exclude_seen: bool = True


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
    # indicator event names, PRIMARY first (UR's eventNames)
    indicators: tuple[str, ...] = ("buy", "view")
    # optional self-cleaning window: {"duration": "30 days", ...}
    event_window: Optional[dict] = None


@dataclass
class IndicatorData:
    name: str
    rows: np.ndarray  # user idx
    cols: np.ndarray  # target idx (into its own target vocab)
    target_vocab: BiMap


@dataclass
class TrainingData(SanityCheck):
    indicators: list[IndicatorData]
    n_users: int
    user_vocab: BiMap

    def sanity_check(self) -> None:
        if not self.indicators or len(self.indicators[0].rows) == 0:
            raise ValueError("no primary indicator events found")


class URDataSource(DataSource, SelfCleaningDataSource):
    def __init__(self, params: DataSourceParams):
        self.params = params
        self.app_name = params.app_name
        self.event_window = (
            EventWindow(**params.event_window) if params.event_window else None
        )

    def read_training(self, ctx: RuntimeContext) -> TrainingData:
        self.clean_persisted_events(ctx)
        store = EventStoreFacade(ctx.storage)
        frame = store.find_frame(
            app_name=self.params.app_name,
            entity_type="user",
            event_names=list(self.params.indicators),
        )
        indicators = []
        for name in self.params.indicators:
            sub = frame.where_event(name)
            mask = sub.target_idx >= 0
            # each indicator gets its own compact target vocabulary
            raw_targets = sub.target_idx[mask]
            uniq = np.unique(raw_targets)
            remap = {int(t): i for i, t in enumerate(uniq)}
            inv_frame = frame.target_vocab.inverse()
            vocab = BiMap({inv_frame(int(t)): i for t, i in remap.items()})
            indicators.append(
                IndicatorData(
                    name=name,
                    rows=sub.entity_idx[mask].astype(np.int32),
                    cols=np.asarray(
                        [remap[int(t)] for t in raw_targets], dtype=np.int32
                    ),
                    target_vocab=vocab,
                )
            )
        return TrainingData(
            indicators=indicators,
            n_users=frame.n_entities,
            user_vocab=frame.entity_vocab,
        )


# -- algorithm --------------------------------------------------------------


@dataclass
class URAlgorithmParams:
    app_name: str
    max_correlators_per_item: int = 50
    # Mahout's maxNumInteractions (the UR's maxEventsPerEventType): the
    # downsampling's cap on a user's and on an item's events of a type
    max_events_per_event_type: int = 500
    max_query_events: int = 100  # recent history depth per indicator
    indicators: Optional[tuple[str, ...]] = None  # default: all from data


@dataclass
class IndicatorModel:
    name: str
    correlator_scores: np.ndarray  # (I, top_n)
    correlator_idx: np.ndarray  # (I, top_n) into its target vocab, -1 pad
    target_vocab: BiMap


class URModel:
    def __init__(
        self,
        item_vocab: BiMap,
        indicator_models: list[IndicatorModel],
        primary_indicator: str,
    ):
        self.item_vocab = item_vocab  # primary target vocab = item space
        self.indicator_models = indicator_models
        self.primary_indicator = primary_indicator
        # the device-resident correlator tables: staged once, on the
        # first batch (warm-up), reused by every dispatch
        self.resident = ResidentCorrelators([
            (m.correlator_idx, m.correlator_scores, len(m.target_vocab))
            for m in indicator_models
        ])

    # the staged tables are serving state, not part of the pickled model
    def __getstate__(self):
        return {
            "item_vocab": self.item_vocab,
            "indicator_models": self.indicator_models,
            "primary_indicator": self.primary_indicator,
        }

    def __setstate__(self, state):
        self.__init__(
            state["item_vocab"],
            state["indicator_models"],
            state["primary_indicator"],
        )

    def resident_device_bytes(self) -> float:
        """Per-device HBM footprint for the tenant cache's budget
        (tenancy/cache.py walks to this hook): the staged tables, else
        the host tables once (staging mirrors them 1:1 but for the
        block pad)."""
        staged = self.resident.device_bytes()
        if staged is not None:
            return staged
        return float(sum(
            m.correlator_idx.nbytes + m.correlator_scores.nbytes
            for m in self.indicator_models
        ))


class URAlgorithm(Algorithm):
    def __init__(self, params: URAlgorithmParams):
        self.params = params

    def train(self, ctx: RuntimeContext, pd: TrainingData) -> URModel:
        """Each indicator's events binarised, grouped by user and
        downsampled on the host, then every indicator's cross-occurrence
        with the primary joined from sorted pairs on the device, in blocks
        (`cco.join_indicators`): no (users × items) matrix at any size."""
        primary = pd.indicators[0]
        wanted = self.params.indicators or tuple(i.name for i in pd.indicators)
        cap = self.params.max_events_per_event_type
        kept = {}
        for m, ind in enumerate(pd.indicators):
            if ind.name not in wanted and ind is not primary:
                continue
            with _spans.span("ur.train.group", indicator=ind.name):
                grouped = cco.group_by_user(
                    ind.rows, ind.cols, pd.n_users, len(ind.target_vocab))
            with _spans.span("ur.train.downsample", indicator=ind.name) as sp:
                kept[ind.name] = cco.downsample(
                    grouped, cap, cco.DOWNSAMPLE_SEED, m)
                sp.attrs["distinct"] = int(grouped.cols.size)
                sp.attrs["kept"] = int(kept[ind.name].cols.size)
            _EVENTS_KEPT.inc(float(kept[ind.name].cols.size),
                             indicator=ind.name)
        joined = [ind for ind in pd.indicators if ind.name in wanted]
        tables, stats = cco.join_indicators(
            kept[primary.name], [kept[ind.name] for ind in joined],
            pd.n_users, self.params.max_correlators_per_item,
            self_first=bool(joined) and joined[0] is primary,
        )
        models = []
        for ind, (scores, idx), pairs in zip(joined, tables, stats["pairs"]):
            _PAIRS.inc(float(pairs), indicator=ind.name)
            models.append(IndicatorModel(
                name=ind.name,
                correlator_scores=scores,
                correlator_idx=idx,
                target_vocab=ind.target_vocab,
            ))
        return URModel(
            item_vocab=primary.target_vocab,
            indicator_models=models,
            primary_indicator=primary.name,
        )

    # -- serving -----------------------------------------------------------
    def _user_histories(
        self,
        ctx: RuntimeContext,
        users: list,
        event_name: str,
        target_vocab: BiMap,
    ) -> list:
        """Per-user history rows for a WHOLE serving micro-batch in ONE
        store round trip (VERDICT r4 #4 — the per-query loop cost one
        store call per (query, indicator); a remote/sharded store paid a
        network RTT each). A read the store fails is logged, counted
        (`ur_history_read_failures_total`) and served as no history."""
        empty = np.empty(0, dtype=np.int64)
        if ctx.storage is None:
            return [empty for _ in users]
        store = EventStoreFacade(ctx.storage)
        try:
            by_user = store.find_by_entities(
                app_name=self.params.app_name,
                entity_type="user",
                entity_ids=users,
                event_names=[event_name],
                limit_per_entity=self.params.max_query_events,
                latest=True,
            )
        except Exception:
            log.exception("history lookup failed for %s", event_name)
            _HISTORY_READ_FAILURES.inc()
            return [empty for _ in users]
        out = []
        for u in users:
            rows = []
            for e in by_user.get(u, ()):
                ix = target_vocab.get(e.target_entity_id)
                if ix is not None:
                    rows.append(ix)
            out.append(np.asarray(rows, dtype=np.int64))
        return out

    #: a blacklist a form long, for warm-up: nothing, the narrow row
    #: list, the wide one, packed words (`cco.exclusion_of`)
    _WARMUP_BLACKLISTS = (0, 1, 9, 65)

    def warmup(self, model: URModel) -> None:
        """Stage the correlator tables into HBM and pre-compile the
        batched serving programs. Shapes are static per params: the
        batch buckets {1, 8, 64} x the four exclusion forms a batch's
        ids can pick, and a bucket's add-only program for a plan that
        outgrows one call; fixed history depth, the k floor — so warming
        these covers live traffic; only a query with num above the k
        floor would compile a further shape."""
        if not model.indicator_models or len(model.item_vocab) == 0:
            return
        from predictionio_tpu.utils.bucket import topk_bucket

        inv = model.item_vocab.inverse()
        n_items = len(model.item_vocab)
        for batch in (1, 8, 64):
            for n_black in self._WARMUP_BLACKLISTS:
                black = [inv(i) for i in range(min(n_black, n_items))]
                self._predict_batch(
                    self.serving_context, model,
                    [Query(user="__warmup__", blacklist=black)] * batch,
                )
            # "__warmup__" has no history, so the batches above made one
            # call each: a plan one window longer than a call's runs the
            # leading, add-only program of this bucket too
            cco.batch_score_topk(
                model.resident.get(),
                np.zeros((cco.call_windows(batch) + 1, 3), np.int64),
                cco.Exclusion("none", None), batch,
                topk_bucket(min(10, n_items), n_items, floor=64),
            )

    # device micro-batch; eval-sized inputs chunk
    _DISPATCH_CHUNK = cco.MAX_BATCH

    def _predict_batch(
        self, ctx: RuntimeContext, model: URModel, queries: list[Query]
    ) -> list[PredictedResult]:
        """The UR serving hot path as one device dispatch per ≤64-query
        chunk (VERDICT r2 #5): host gathers per-query histories from the
        event store, the device scores every (query, item) pair across all
        indicators, applies the sparse per-query exclusion sets, and
        top-ks."""
        if len(queries) > self._DISPATCH_CHUNK:
            out: list[PredictedResult] = []
            for lo in range(0, len(queries), self._DISPATCH_CHUNK):
                out.extend(self._predict_batch(
                    ctx, model, queries[lo : lo + self._DISPATCH_CHUNK]
                ))
            return out
        from predictionio_tpu.utils.bucket import batch_bucket, topk_bucket

        n_real = len(queries)
        n_items = len(model.item_vocab)
        if n_items == 0 or not model.indicator_models:
            return [PredictedResult() for _ in queries]
        # the three host/device phases of a batch are spans, as the ALS
        # path's are: what the host does before the device pass (the
        # live history read, its own span, is most of it), the pass
        # until the answers are host arrays, the decode back to item ids
        with _spans.span("ur.predict.prepare") as sp:
            bsz = batch_bucket(n_real)
            sp.attrs["live"] = n_real
            sp.attrs["bucket"] = bsz
            h_max = self.params.max_query_events
            users = [q.user for q in queries]
            with _spans.span("ur.history_read") as hr:
                per_indicator = {
                    ind.name: self._user_histories(
                        ctx, users, ind.name, ind.target_vocab
                    )
                    for ind in model.indicator_models
                }
                # seen-filter works in the PRIMARY item space; the
                # primary indicator's read serves it where the model
                # keeps that indicator (same store call, same vocab)
                seen = per_indicator.get(model.primary_indicator)
                if seen is None and any(q.exclude_seen for q in queries):
                    seen = self._user_histories(
                        ctx, users, model.primary_indicator,
                        model.item_vocab,
                    )
                hr.attrs["events"] = int(sum(
                    len(h) for hs in per_indicator.values() for h in hs
                ))
            histories = []
            for ind in model.indicator_models:
                h = np.full((bsz, h_max), -1, np.int32)
                for qi, hist in enumerate(per_indicator[ind.name]):
                    h[qi, : len(hist)] = hist[:h_max]
                histories.append(h)
            lists = []
            for qi, q in enumerate(queries):
                ex = [int(ix) for ix in seen[qi]] if q.exclude_seen else []
                for it in q.blacklist or []:
                    ix = model.item_vocab.get(it)
                    if ix is not None:
                        ex.append(ix)
                lists.append(ex)
            staged = model.resident.get()
            # which postings the batch reads is host arithmetic on the
            # staged offsets: part of prepare, not of the device pass
            plan = cco.plan_windows(staged, histories)
            sp.attrs["windows"] = len(plan)
            # the form follows the ids this batch carries: nothing, a
            # row list, or words beyond ROWLIST_MAX ids a query — no
            # static worst-case width, no host mask
            exclude = cco.exclusion_of(lists, bsz, staged.rows_padded)
            sp.attrs["form"] = exclude.form
            _BATCHES.inc(form=exclude.form)
            _EXCLUSION_BYTES.inc(float(exclude.nbytes))
            k_req = min(max((q.num for q in queries), default=10), n_items)
            k = topk_bucket(k_req, n_items, floor=64)
        with _spans.span("ur.predict.device"):
            # padding-waste accounting (ISSUE 3) at the pad site: n_real
            # live queries ran in a bsz-shaped device program
            prof0 = _devprof.snapshot()
            vals, idx = cco.batch_score_topk(staged, plan, exclude, bsz, k)
            _devprof.record_batch_padding(
                n_real, bsz, flops=_devprof.snapshot().flops - prof0.flops
            )
        with _spans.span("ur.predict.decode"):
            inv = model.item_vocab.inverse()
            out = []
            for qi, q in enumerate(queries):
                scores = []
                for v, ix in zip(vals[qi], idx[qi]):
                    if len(scores) >= q.num:
                        break
                    if v <= 0.0:  # positive_only: no LLR evidence, or
                        break  # excluded — and the rest is no higher
                    scores.append(
                        ItemScore(item=inv(int(ix)), score=float(v))
                    )
                out.append(PredictedResult(item_scores=scores))
        return out

    def predict(self, model: URModel, query: Query) -> PredictedResult:
        return self._predict_batch(self.serving_context, model, [query])[0]

    def batch_predict(self, ctx, model: URModel, queries):
        preds = self._predict_batch(
            ctx or self.serving_context, model, [q for _, q in queries]
        )
        return [(qx, p) for (qx, _q), p in zip(queries, preds)]


class UniversalRecommenderEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            URDataSource,
            IdentityPreparator,
            {"ur": URAlgorithm},
            FirstServing,
        )
