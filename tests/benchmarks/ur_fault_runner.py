"""Drive a run of the Universal Recommender cell with the program's timed
path broken underneath.

    python3 tests/benchmarks/ur_fault_runner.py <fault> <run.py's arguments>

A process of its own (the faults patch the program's modules), started by
tests/benchmarks/test_ur_cell.py, which expects `correct: false`.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _patch_predict(edit) -> None:
    from predictionio_tpu.engines.universal import engine

    real = engine.URAlgorithm._predict_batch

    def predict(self, ctx, model, queries):
        results = real(self, ctx, model, queries)
        for r in results:
            if r.item_scores:
                edit(r, model)
        return results

    engine.URAlgorithm._predict_batch = predict


def item_altered() -> None:
    """Every answer's first item is swapped for the catalogue's last."""
    def edit(r, model):
        r.item_scores[0].item = f"i{len(model.item_vocab) - 1}"

    _patch_predict(edit)


def reply_dropped() -> None:
    """Answers come one item short."""
    _patch_predict(lambda r, model: r.item_scores.pop())


def history_dropped() -> None:
    """One indicator's history never reaches the scoring program."""
    import numpy as np

    from predictionio_tpu.engines.universal import engine

    real = engine.URAlgorithm._user_histories

    def histories(self, ctx, users, event_name, target_vocab):
        if event_name == "cart":
            return [np.empty(0, np.int64) for _ in users]
        return real(self, ctx, users, event_name, target_vocab)

    engine.URAlgorithm._user_histories = histories


def store_read_fails() -> None:
    """The event store fails every serving-time read: the program serves
    empty histories — and must not pass as "no recommendations"."""
    from predictionio_tpu.data.store import event_store

    def broken(self, **kwargs):
        raise RuntimeError("event store down")

    event_store.EventStoreFacade.find_by_entities = broken


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    if "--rehearsal" in argv:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    globals()[fault]()
    from benchmarks import run

    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
