"""Drive a benchmark run with the program's timed path broken underneath.

    python3 tests/benchmarks/fault_runner.py <fault> <run.py's arguments>

A process of its own (the faults patch the program's modules), started by
tests/benchmarks/test_benchmark_smoke.py, which expects `correct: false`.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def state_unchanged() -> None:
    """The train program returns its state as it got it: no iteration."""
    from predictionio_tpu.models import als

    real = als.StagedDenseTrain.run

    def run(self):
        self.static_kwargs = dict(self.static_kwargs, iterations=0)
        return real(self)

    als.StagedDenseTrain.run = run


def half_left_out() -> None:
    """Staging leaves out every second pair."""
    from predictionio_tpu.models import als

    real = als.stage_dense

    def stage(rows, cols, vals, *a, **kw):
        kw.pop("user_deg", None), kw.pop("item_deg", None)
        return real(rows[::2], cols[::2], vals[::2], *a, **kw)

    als.stage_dense = stage


def row_altered() -> None:
    """One row of the answer is altered where it is produced."""
    from predictionio_tpu.models import als

    real = als.StagedDenseTrain.factors

    def factors(self, uf, itf):
        u, i = real(self, uf, itf)
        import numpy as np

        i = i.copy()
        i[np.argsort(np.linalg.norm(i, axis=1))[3 * len(i) // 4]] *= 1.5
        return u, i

    als.StagedDenseTrain.factors = factors


def _patch_predict(edit) -> None:
    from predictionio_tpu.engines.recommendation import engine

    real = engine.ALSAlgorithm._predict_batch

    def predict(self, model, queries):
        results = real(self, model, queries)
        for r in results:
            if r.item_scores:
                edit(r, model)
        return results

    engine.ALSAlgorithm._predict_batch = predict


def item_altered() -> None:
    """Every answer's first item is swapped for the catalog's last."""
    def edit(r, model):
        last = model.factors.item_factors.shape[0] - 1
        r.item_scores[0].item = f"i{last}"

    _patch_predict(edit)


def score_altered() -> None:
    def edit(r, model):
        r.item_scores[0].score *= 1.05

    _patch_predict(edit)


def reply_dropped() -> None:
    """Answers come one item short."""
    def edit(r, model):
        r.item_scores.pop()

    _patch_predict(edit)


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    if "--rehearsal" in argv:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    globals()[fault]()
    from benchmarks import run

    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
