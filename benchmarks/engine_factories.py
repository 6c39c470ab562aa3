"""Engine factories the benchmark owns.

`InMemoryRecommendationEngine` is the recommendation template with one stage
swapped, exactly as `FileRecommendationEngine` swaps it: the data source
hands over a corpus that set-up has already made in memory; preparator,
algorithm and serving are the template's own. The DASE contract asks a data
source for `read_training` only. Reading 57 M interactions back from a text
file (~26 s per 20 M lines) or the event store (~35k events/s bulk ingest,
PR 21) does not fit a run's set-up, so the read stage is outside the train
cell — PERF.md says so.
"""

from __future__ import annotations

from dataclasses import dataclass

from predictionio_tpu.controller import (
    DataSource,
    Engine,
    EngineFactory,
    FirstServing,
    IdentityPreparator,
)
from predictionio_tpu.engines.recommendation.engine import (
    ALSAlgorithm,
    TrainingData,
)

FACTORY = "benchmarks.engine_factories.InMemoryRecommendationEngine"


def variant_of(config: dict, algorithm: dict | None = None) -> dict:
    """The engine variant (what an engine.json holds) of a configuration."""
    return {
        "id": config["name"],
        "engineFactory": FACTORY,
        "datasource": {"params": {"corpus": config["name"]}},
        "algorithms": [{"name": "als",
                        "params": dict(algorithm or config["algorithm"])}],
        "mesh": config.get("mesh"),
    }


#: corpora by key, put there by the driver's set-up; a data source is built
#: from JSON parameters alone, so the arrays travel by name
CORPORA: dict[str, TrainingData] = {}


@dataclass
class InMemoryDataSourceParams:
    corpus: str


class InMemoryRatingsDataSource(DataSource):
    def __init__(self, params: InMemoryDataSourceParams):
        self.params = params

    def read_training(self, ctx) -> TrainingData:
        return CORPORA[self.params.corpus]


class InMemoryRecommendationEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            InMemoryRatingsDataSource,
            IdentityPreparator,
            {"als": ALSAlgorithm},
            FirstServing,
        )
