"""Item-similarity engine: exact column-cosine (the DIMSUM workload).

Reference: the experimental DIMSUM demo (examples/experimental/ — Spark
MLlib RowMatrix.columnSimilarities with sampling). On TPU the item-item
Gram matrix is one dense MXU matmul, so similarities are exact
(models/dimsum.py documents why sampling is obsolete here).

Shape: DataSource folds user→item interactions into a weighted indicator
matrix; the algorithm computes each item's top-N cosine-similar items
once at train time; serving sums similarity scores over the queried
items (multi-item queries rank by total similarity to the basket).
"""

from __future__ import annotations

from dataclasses import dataclass, field

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
from predictionio_tpu.data.store.bimap import BiMap
from predictionio_tpu.data.store.event_store import EventStoreFacade
from predictionio_tpu.models import als, dimsum
from predictionio_tpu.models.resident import ResidentServing


@dataclass
class Query:
    items: list[str] = field(default_factory=list)
    num: int = 10


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
    event_names: tuple[str, ...] = ("view", "buy")
    entity_type: str = "user"


@dataclass
class TrainingData(SanityCheck):
    matrix: np.ndarray  # (U, I) weighted indicator
    item_vocab: BiMap

    def sanity_check(self) -> None:
        if self.matrix.size == 0 or not self.matrix.any():
            raise ValueError("no user→item interactions found")


class ItemSimDataSource(DataSource):
    def __init__(self, params: DataSourceParams):
        self.params = params

    def read_training(self, ctx: RuntimeContext) -> TrainingData:
        frame = EventStoreFacade(ctx.storage).find_frame(
            app_name=self.params.app_name,
            entity_type=self.params.entity_type,
            event_names=list(self.params.event_names),
        )
        mask = frame.target_idx >= 0
        users = frame.entity_idx[mask]
        items = frame.target_idx[mask]
        m = np.zeros((frame.n_entities, frame.n_targets), dtype=np.float32)
        np.add.at(m, (users, items), 1.0)
        return TrainingData(matrix=m, item_vocab=frame.target_vocab)


@dataclass
class ItemSimAlgorithmParams:
    top_n: int = 50  # similar items kept per item
    # sharded serving (ISSUE 11 satellite, carried fleet follow-up):
    # instead of the train-time O(I²) top-N precompute, keep the item
    # COLUMN vectors (the (I, U) transpose of the indicator matrix)
    # row-sharded across the serving mesh and compute each query item's
    # top-N cosine on the fly (fleet.ShardedRuntime.similar_items) —
    # the catalog (and the U-dim vectors) can exceed one chip's HBM,
    # and item-vocab growth needs no O(I²) recompute.
    shard_serving: bool = False
    # serving dtype for the on-the-fly cosine vectors (ISSUE 14):
    # "int8" per-row-quantizes the (I, U) column vectors (~1/4 the
    # resident bytes — the U dim is the expensive one here), "bf16"
    # halves them; cosine normalizes by the STAGED f32 norms either
    # way. Applies to both the single-device staged state and the
    # sharded tier.
    serve_dtype: str = "f32"


@dataclass
class ItemSimModel:
    sim_scores: np.ndarray  # (I, top_n) — empty when shard_serving
    sim_idx: np.ndarray  # (I, top_n), -1 padded
    item_vocab: BiMap
    top_n: int = 50
    # shard_serving: the raw (I, U) item column vectors; similarity is
    # computed on the fly from the sharded copies
    item_vectors: object = None  # Optional[np.ndarray]
    # serving dtype for the staged/sharded on-the-fly cosine (ISSUE 14)
    serve_dtype: str = "f32"

    def __post_init__(self):
        # on-the-fly cosine off resident column vectors: a zero-row user
        # side, since `similar_items` is the only verb that serves here
        self.resident = None
        if self.item_vectors is not None:
            self.resident = ResidentServing(
                als.ALSFactors(
                    np.zeros((0, self.item_vectors.shape[1]), np.float32),
                    self.item_vectors, None, self.item_vocab,
                ),
                self.serve_dtype,
            )

    def __setstate__(self, state):
        # models pickled BEFORE these fields existed must keep loading
        state.setdefault("top_n", 50)
        state.setdefault("item_vectors", None)
        state.setdefault("serve_dtype", "f32")
        self.__dict__.update(state)
        if "resident" not in state:
            self.__post_init__()

    def sharded_info(self):
        if self.resident is None:
            return None
        return self.resident.sharded_info()


class ItemSimAlgorithm(Algorithm):
    def __init__(self, params: ItemSimAlgorithmParams):
        self.params = params

    def train(self, ctx: RuntimeContext, pd: TrainingData) -> ItemSimModel:
        if self.params.shard_serving:
            # keep the column vectors; similarity is served on the fly
            # from the sharded copies — no O(I²) precompute
            empty = np.zeros((0, 0), np.float32)
            return ItemSimModel(
                sim_scores=empty,
                sim_idx=empty.astype(np.int64),
                item_vocab=pd.item_vocab,
                top_n=self.params.top_n,
                item_vectors=np.ascontiguousarray(
                    pd.matrix.T.astype(np.float32)
                ),
                serve_dtype=getattr(self.params, "serve_dtype", "f32"),
            )
        scores, idx = dimsum.column_cosine_topn(
            pd.matrix, top_n=self.params.top_n, mesh=ctx.mesh
        )
        return ItemSimModel(
            sim_scores=scores, sim_idx=idx, item_vocab=pd.item_vocab,
            top_n=self.params.top_n,
        )

    def _basket_rows(self, model: ItemSimModel, query: Query):
        return [
            model.item_vocab.get(i)
            for i in query.items
            if model.item_vocab.get(i) is not None
        ]

    def predict(self, model: ItemSimModel, query: Query) -> PredictedResult:
        n_items = len(model.item_vocab)
        known = self._basket_rows(model, query)
        if not known:
            return PredictedResult()
        total = np.zeros(n_items, dtype=np.float32)
        if model.item_vectors is not None:
            # on-the-fly similarity (shard_serving): sharded when > 1
            # device is visible, the STAGED fused cosine otherwise
            # (ISSUE 14 — als.similar_serving off the resident column
            # vectors; the per-query numpy cosine matmul is retired) —
            # both truncate to top_n per query item exactly like the
            # precomputed path
            k = min(model.top_n, n_items)
            vals, idx = model.resident.similar_items(
                np.asarray(known, np.int64), k, exclude_self=True,
                shard=True,
            )
            from predictionio_tpu.ops.topk import NEG_INF

            for r in range(len(known)):
                ok = vals[r] > NEG_INF / 2
                np.add.at(total, idx[r][ok], vals[r][ok])
        else:
            for row in known:
                idx = model.sim_idx[row]
                ok = idx >= 0
                np.add.at(total, idx[ok], model.sim_scores[row][ok])
        total[known] = 0.0  # never recommend the queried items themselves
        top = np.argsort(-total)[: query.num]
        inv = model.item_vocab.inverse()
        return PredictedResult(
            item_scores=[
                ItemScore(item=inv(int(ix)), score=float(total[ix]))
                for ix in top
                if total[ix] > 0.0
            ]
        )


class ItemSimilarityEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            ItemSimDataSource,
            IdentityPreparator,
            {"dimsum": ItemSimAlgorithm},
            FirstServing,
        )
