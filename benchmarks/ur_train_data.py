"""The Universal Recommender train cell's data and its in-memory data source.

Every event of the source's shape is made from `--seed`, in memory: per
behaviour type the source's share of `n_behaviours` (largest remainder, so
the four types sum to it exactly and every seed makes the same number), laid
over the users by a multinomial whose weights are log-normal (sigma 1): a
heavy-tailed count a user with the type's mean, as `ur_data.user_history`
draws one for the serving cell, but no store cap; items by popularity
rank^-1/2, drawn by `ur_data.draw_items`. The users come in a random order,
as an event log interleaves them. A pair may repeat: the engine binarises.

`InMemoryUREngine` is the Universal Recommender with one stage swapped, as
`engine_factories.InMemoryRecommendationEngine` swaps the recommendation
template's: the data source hands over the events set-up made; preparator,
algorithm and serving are the engine's own. Reading 100 M events back from
an event store does not fit a run's set-up (ROADMAP R4), so the read stage
is outside the cell.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from benchmarks import ur_data
from predictionio_tpu.controller import (
    DataSource,
    Engine,
    EngineFactory,
    FirstServing,
    IdentityPreparator,
)
from predictionio_tpu.engines.universal.engine import (
    IndicatorData,
    TrainingData,
    URAlgorithm,
)

FACTORY = "benchmarks.ur_train_data.InMemoryUREngine"


def type_totals(cfg: dict) -> dict:
    """Events of each type: the source's shares of `n_behaviours`, rounded
    by largest remainder so that they sum to it."""
    names = list(cfg["indicators"])
    shares = np.array([cfg["behaviour_shares"][n] for n in names], float)
    exact = cfg["n_behaviours"] * shares / shares.sum()
    totals = np.floor(exact).astype(np.int64)
    short = int(cfg["n_behaviours"] - totals.sum())
    totals[np.argsort(totals - exact)[:short]] += 1
    return dict(zip(names, (int(t) for t in totals)))


def make_events(cfg: dict, seed: int) -> dict:
    """{indicator: (user rows int32, item rows int32)}, each type's events
    in a random order of users."""
    n_users = int(cfg["n_users"])
    sigma = float(cfg["events"]["lognormal_sigma"])
    draw_cfg = {"n_items": cfg["n_items"],
                "tables": {"popularity_exponent":
                           cfg["events"]["popularity_exponent"]}}
    out = {}
    for m, (name, total) in enumerate(type_totals(cfg).items()):
        rng = np.random.default_rng([seed % (2**32), 39, m])
        weights = rng.lognormal(0.0, sigma, n_users)
        per_user = rng.multinomial(total, weights / weights.sum())
        rows = np.repeat(np.arange(n_users, dtype=np.int32), per_user)
        rng.shuffle(rows)
        cols = ur_data.draw_items(rng, total, draw_cfg).astype(np.int32)
        out[name] = (rows, cols)
    return out


def training_data(cfg: dict, events: dict, item_vocab, user_vocab):
    """The engine's `TrainingData`: every indicator's targets are items, so
    one catalogue vocabulary serves all four."""
    return TrainingData(
        indicators=[
            IndicatorData(name=name, rows=rows, cols=cols,
                          target_vocab=item_vocab)
            for name, (rows, cols) in events.items()
        ],
        n_users=int(cfg["n_users"]),
        user_vocab=user_vocab,
    )


def variant_of(config: dict) -> dict:
    """The engine variant (what an engine.json holds) of the configuration."""
    return {
        "id": config["name"],
        "engineFactory": FACTORY,
        "datasource": {"params": {"corpus": config["name"]}},
        "algorithms": [{"name": "ur", "params": dict(config["algorithm"])}],
    }


#: training data by key, put there by the driver's set-up
CORPORA: dict = {}


@dataclass
class InMemoryURDataSourceParams:
    corpus: str


class InMemoryURDataSource(DataSource):
    def __init__(self, params: InMemoryURDataSourceParams):
        self.params = params

    def read_training(self, ctx) -> TrainingData:
        return CORPORA[self.params.corpus]


class InMemoryUREngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            InMemoryURDataSource,
            IdentityPreparator,
            {"ur": URAlgorithm},
            FirstServing,
        )
