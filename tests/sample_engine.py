"""Deterministic id-stamping fake engines for workflow tests.

The key test pattern of the reference (core/src/test/scala/io/prediction/
controller/SampleEngine.scala, 472 LoC): every DASE stage stamps its params
id into the objects flowing through, so tests assert the exact data path
without any real ML.
"""

from __future__ import annotations

import pickle
from dataclasses import dataclass, field

from predictionio_tpu.controller import (
    Algorithm,
    DataSource,
    EngineFactory,
    Engine,
    FirstServing,
    LocalFileSystemPersistentModel,
    Preparator,
    SanityCheck,
    Serving,
)


# -- data carriers ----------------------------------------------------------


@dataclass
class TrainingData(SanityCheck):
    id: int
    error: bool = False

    def sanity_check(self):
        if self.error:
            raise ValueError(f"training data {self.id} is dirty")


@dataclass
class PreparedData:
    td_id: int
    p_id: int


@dataclass
class EvalInfo:
    id: int


@dataclass
class Query:
    q: int
    supplemented: bool = False


@dataclass
class Actual:
    q: int


@dataclass
class Prediction:
    q: int
    algo_id: int
    td_id: int
    p_id: int
    supplemented: bool = False


# -- params -----------------------------------------------------------------


@dataclass
class DSP:
    id: int = 0
    error: bool = False


@dataclass
class PP:
    id: int = 0


@dataclass
class AP:
    id: int = 0


# -- stages -----------------------------------------------------------------


class DataSource0(DataSource):
    def __init__(self, params: DSP):
        self.params = params

    def read_training(self, ctx):
        return TrainingData(id=self.params.id, error=self.params.error)

    def read_eval(self, ctx):
        return [
            (
                TrainingData(id=self.params.id),
                EvalInfo(id=s),
                [(Query(q=10 * s + i), Actual(q=10 * s + i)) for i in range(3)],
            )
            for s in range(2)
        ]


class Preparator0(Preparator):
    def __init__(self, params: PP):
        self.params = params

    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        return PreparedData(td_id=td.id, p_id=self.params.id)


@dataclass
class Model0:
    algo_id: int
    td_id: int
    p_id: int


class Algo0(Algorithm):
    def __init__(self, params: AP):
        self.params = params

    def train(self, ctx, pd: PreparedData) -> Model0:
        return Model0(algo_id=self.params.id, td_id=pd.td_id, p_id=pd.p_id)

    def predict(self, model: Model0, query: Query) -> Prediction:
        return Prediction(
            q=query.q,
            algo_id=model.algo_id,
            td_id=model.td_id,
            p_id=model.p_id,
            supplemented=query.supplemented,
        )


class Algo1(Algo0):
    """Same behavior, distinct class for multi-algo binding tests."""


class NoParamsAlgo(Algorithm):
    """Zero-arg constructor → Doer's no-params path."""

    def train(self, ctx, pd: PreparedData) -> Model0:
        return Model0(algo_id=-1, td_id=pd.td_id, p_id=pd.p_id)

    def predict(self, model, query):
        return Prediction(
            q=query.q, algo_id=-1, td_id=model.td_id, p_id=model.p_id
        )


@dataclass
class PersistentModel0(LocalFileSystemPersistentModel):
    """User-managed persistence path (PersistentModelManifest mode)."""

    algo_id: int = 0
    td_id: int = 0
    p_id: int = 0


class PersistentAlgo(Algorithm):
    def __init__(self, params: AP):
        self.params = params

    def train(self, ctx, pd: PreparedData) -> PersistentModel0:
        return PersistentModel0(
            algo_id=self.params.id, td_id=pd.td_id, p_id=pd.p_id
        )

    def predict(self, model, query):
        return Prediction(
            q=query.q, algo_id=model.algo_id, td_id=model.td_id, p_id=model.p_id
        )


class UnserializableModel:
    """Defeats pickle → RetrainOnDeploy path."""

    def __init__(self, algo_id, td_id, p_id):
        self.algo_id, self.td_id, self.p_id = algo_id, td_id, p_id
        self.closure = lambda: None  # not picklable

    def __reduce__(self):
        raise pickle.PicklingError("deliberately unserializable")


class UnserializableAlgo(Algorithm):
    def __init__(self, params: AP):
        self.params = params

    def train(self, ctx, pd: PreparedData):
        return UnserializableModel(self.params.id, pd.td_id, pd.p_id)

    def predict(self, model, query):
        return Prediction(
            q=query.q, algo_id=model.algo_id, td_id=model.td_id, p_id=model.p_id
        )


class SupplementServing(Serving):
    """Stamps supplement + serves first prediction."""

    def supplement(self, query: Query) -> Query:
        return Query(q=query.q, supplemented=True)

    def serve(self, query, predictions):
        return predictions[0]


class SumServing(Serving):
    """Combines multi-algo predictions: sums algo ids."""

    def serve(self, query, predictions):
        p = predictions[0]
        return Prediction(
            q=p.q,
            algo_id=sum(x.algo_id for x in predictions),
            td_id=p.td_id,
            p_id=p.p_id,
            supplemented=p.supplemented,
        )


# -- engines ----------------------------------------------------------------


@dataclass
class SlowDSP:
    id: int = 0
    sleep_s: float = 30.0


class SlowDataSource(DataSource):
    """Sleeps through read_training — scheduler chaos tests kill the
    train subprocess while it sits here."""

    def __init__(self, params: SlowDSP):
        self.params = params

    def read_training(self, ctx):
        import time

        time.sleep(self.params.sleep_s)
        return TrainingData(id=self.params.id)


class Engine0Factory(EngineFactory):
    def apply(self):
        return Engine(
            DataSource0,
            Preparator0,
            {"algo0": Algo0, "algo1": Algo1, "noparams": NoParamsAlgo},
            {"": FirstServing, "sum": SumServing, "supp": SupplementServing},
        )


class PersistentEngineFactory(EngineFactory):
    def apply(self):
        return Engine(DataSource0, Preparator0, PersistentAlgo, FirstServing)


class SlowEngineFactory(EngineFactory):
    def apply(self):
        return Engine(SlowDataSource, Preparator0, Algo0, FirstServing)


class UnserializableEngineFactory(EngineFactory):
    def apply(self):
        return Engine(DataSource0, Preparator0, UnserializableAlgo, FirstServing)


# -- fleet-eval grid engine (ISSUE 20) --------------------------------------
# A jax-free engine with a real eval surface: configurable folds, a
# train_grid hook that stamps how many points shared its device program,
# and a deterministic score peaked at weight=0.37 so grid winners are
# known in advance. Evalfleet chaos/parity tests use it.


@dataclass
class GridDSP:
    folds: int = 2
    queries: int = 4
    sleep_s: float = 0.0  # stall inside read_eval → kill lands mid-shard


class GridDataSource(DataSource):
    def __init__(self, params: GridDSP):
        self.params = params

    def read_training(self, ctx):
        return TrainingData(id=0)

    def read_eval(self, ctx):
        if self.params.sleep_s:
            import time

            time.sleep(self.params.sleep_s)
        return [
            (
                TrainingData(id=f),
                EvalInfo(id=f),
                [
                    (Query(q=100 * f + i), Actual(q=100 * f + i))
                    for i in range(self.params.queries)
                ],
            )
            for f in range(self.params.folds)
        ]


@dataclass
class GridAP:
    weight: float = 0.0
    # simulated device-program cost: train_grid pays it ONCE for the
    # whole params group (one program), train() pays it per point
    train_cost_s: float = 0.0


@dataclass
class GridModel:
    weight: float
    td_id: int
    grid_size: int = 1  # points trained in the same train_grid call


@dataclass
class GridPrediction:
    q: int
    score: float
    grid_size: int


class GridAlgo(Algorithm):
    BEST_WEIGHT = 0.37

    def __init__(self, params: GridAP):
        self.params = params

    @staticmethod
    def _spend(cost_s: float) -> None:
        if cost_s:
            import time

            time.sleep(cost_s)

    def train(self, ctx, pd) -> GridModel:
        self._spend(self.params.train_cost_s)
        return GridModel(self.params.weight, pd.td_id, 1)

    def train_grid(self, ctx, pd, params_list) -> list:
        self._spend(max(p.train_cost_s for p in params_list))
        return [
            GridModel(p.weight, pd.td_id, len(params_list))
            for p in params_list
        ]

    def predict(self, model: GridModel, query: Query) -> GridPrediction:
        return GridPrediction(
            q=query.q,
            score=1.0 - abs(model.weight - self.BEST_WEIGHT),
            grid_size=model.grid_size,
        )


class GridScore:
    """AverageMetric over GridPrediction.score (declared lazily so
    importing sample_engine needs no controller.metrics / numpy)."""

    def __new__(cls):
        from predictionio_tpu.controller.metrics import AverageMetric

        class _GridScore(AverageMetric):
            def header(self):
                return "GridScore"

            def calculate_one(self, q, p, a):
                return p.score

        return _GridScore()


class GridEngineFactory(EngineFactory):
    def apply(self):
        return Engine(GridDataSource, Preparator0, {"grid": GridAlgo},
                      FirstServing)
