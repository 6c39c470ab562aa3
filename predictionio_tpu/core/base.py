"""Typeless DASE runtime base — the L4 layer every engine builds on.

Re-design of the reference's `core` package (BaseDataSource.scala:31,
BasePreparator.scala:30, BaseAlgorithm.scala:55, BaseServing.scala:28,
BaseEngine.scala:35, BaseEvaluator.scala:36, AbstractDoer.scala:32).

Key departures from the reference, driven by the TPU runtime model:
- The reference threads a `SparkContext` through every stage; here the
  equivalent ambient handle is a `RuntimeContext`: storage registry +
  optional device `Mesh` + workflow params. Data stages return host
  columnar structures / numpy; algorithms stage them into device arrays.
- The reference's P/L/P2L split (RDD-backed vs local models) collapses:
  every model is host-visible Python state whose array leaves may live in
  HBM. `batch_predict` is first-class (not an afterthought) because eval
  throughput on TPU comes from batching queries into one device program.
- `Doer` reflection (constructor-with-Params vs zero-arg) becomes plain
  signature inspection.
"""

from __future__ import annotations

import inspect
from dataclasses import dataclass, field
from typing import Any, Generic, Optional, Sequence, TypeVar

TD = TypeVar("TD")  # training data
EI = TypeVar("EI")  # eval info
PD = TypeVar("PD")  # prepared data
M = TypeVar("M")  # model
Q = TypeVar("Q")  # query
P = TypeVar("P")  # predicted result
A = TypeVar("A")  # actual result
R = TypeVar("R")  # evaluator result


@dataclass
class WorkflowParams:
    """Reference WorkflowParams.scala:29."""

    batch: str = ""
    verbose: int = 2
    save_model: bool = True
    skip_sanity_check: bool = False
    stop_after_read: bool = False
    stop_after_prepare: bool = False
    # when set, the train run is wrapped in jax.profiler.trace(profile_dir)
    # (SURVEY §5: XLA profiler hook; `pio train --profile DIR`)
    profile_dir: Optional[str] = None


@dataclass
class RuntimeContext:
    """Ambient runtime handle passed to every DASE stage (the re-design of
    the reference's SparkContext created in WorkflowContext.scala:26-45).

    `mesh` is None for single-chip runs; train workflows construct it from
    the engine variant's `mesh` config (parallel/mesh.py:MeshConf)."""

    storage: Any = None  # data.storage.registry.Storage (untyped: layering)
    mesh: Any = None  # Optional[jax.sharding.Mesh]
    mode: str = "train"  # train | eval | serve
    workflow_params: WorkflowParams = field(default_factory=WorkflowParams)
    # the EngineInstance id of the current train run ("" outside train
    # workflows) — keys mid-training checkpoints in MODELDATA
    instance_id: str = ""

    @property
    def is_serving(self) -> bool:
        return self.mode == "serve"


class SanityCheck:
    """Opt-in data validation hook invoked by the train workflow on
    TD/PD/models (reference controller/SanityCheck.scala, called from
    Engine.scala:649-705)."""

    def sanity_check(self) -> None:
        raise NotImplementedError


class StopAfterReadInterruption(Exception):
    """Debug stop-point: --stop-after-read (reference Engine.scala:663)."""


class StopAfterPrepareInterruption(Exception):
    """Debug stop-point: --stop-after-prepare (reference Engine.scala:684)."""


@dataclass(frozen=True)
class PersistentModelManifest:
    """Marker stored in the serialized model list for models persisted by
    the user's own PersistentModel.save (reference workflow package)."""

    class_name: str


def doer(cls: type, params: Any) -> Any:
    """Instantiate a controller class: with its Params if the constructor
    declares one, else zero-arg (reference Doer.apply, AbstractDoer.scala:32-66).

    The decision mirrors the reference's constructor-type check: a first
    positional parameter ANNOTATED as a params dataclass receives the
    params object (defaulted or not); a required positional without such
    an annotation also receives it (duck-typed templates); a constructor
    with only defaulted non-params arguments is called zero-arg."""
    from predictionio_tpu.controller.params import params_class_of

    if params_class_of(cls) is not None:
        return cls(params)
    try:
        sig = inspect.signature(cls.__init__)
    except (TypeError, ValueError):
        return cls()
    n_required = sum(
        1
        for name, p in sig.parameters.items()
        if name != "self"
        and p.kind in (p.POSITIONAL_OR_KEYWORD, p.POSITIONAL_ONLY)
        and p.default is p.empty
    )
    if n_required >= 1:
        return cls(params)
    return cls()


class BaseDataSource(Generic[TD, EI, Q, A]):
    """Reference BaseDataSource.scala:31-52."""

    def read_training(self, ctx: RuntimeContext) -> TD:
        raise NotImplementedError

    def read_eval(
        self, ctx: RuntimeContext
    ) -> list[tuple[TD, EI, list[tuple[Q, A]]]]:
        """Eval sets: (training data, eval info, [(query, actual)])."""
        return []


class BasePreparator(Generic[TD, PD]):
    """Reference BasePreparator.scala:30-42."""

    def prepare(self, ctx: RuntimeContext, td: TD) -> PD:
        raise NotImplementedError


class BaseAlgorithm(Generic[PD, M, Q, P]):
    """Reference BaseAlgorithm.scala:55-123."""

    # serving-time context injected by the deploy server so predict() can
    # read the event store live (the reference reaches the same state via
    # the global Storage singleton behind LEventStore — LEventStore.scala:32)
    _serving_ctx: Optional[RuntimeContext] = None

    def set_serving_context(self, ctx: RuntimeContext) -> None:
        self._serving_ctx = ctx

    @property
    def serving_context(self) -> RuntimeContext:
        return self._serving_ctx if self._serving_ctx is not None else RuntimeContext(mode="serve")

    def train(self, ctx: RuntimeContext, pd: PD) -> M:
        raise NotImplementedError

    def predict(self, model: M, query: Q) -> P:
        raise NotImplementedError

    def batch_predict(
        self, ctx: RuntimeContext, model: M, queries: list[tuple[int, Q]]
    ) -> list[tuple[int, P]]:
        """Bulk predict for eval. Default maps `predict` per query
        (reference P2LAlgorithm.batchPredict:65); TPU algorithms override
        to batch queries into one device program."""
        return [(qx, self.predict(model, q)) for qx, q in queries]

    def query_serializer(self) -> Optional[Any]:
        """Optional custom query/result serde (reference
        CustomQuerySerializer.scala: `querySerializer` formats attached to
        an algorithm, e.g. the regression example's VectorSerializer).
        Return an object with `query_from_json(parsed_json) -> Q` and/or
        `result_to_json(prediction) -> jsonable`; either may be absent.
        When set, the deploy server hands it the RAW parsed JSON (not
        necessarily an object) instead of dataclass extraction."""
        return None

    def query_class(self) -> Optional[type]:
        """Query type for JSON extraction at serving time (reference
        BaseAlgorithm.queryClass via TypeResolver). Resolved from the
        `predict` signature's `query` annotation when present."""
        import typing

        try:
            # get_type_hints, not raw signature annotations: under
            # `from __future__ import annotations` the latter are strings
            hints = typing.get_type_hints(self.predict)
            ann = hints.get("query")
            return ann if isinstance(ann, type) else None
        except (TypeError, ValueError, NameError):
            return None

    def make_persistent_model(
        self, model_id: str, model: M, params: Any
    ) -> Any:
        """Decide the persistence mode for a trained model (reference
        BaseAlgorithm.makePersistentModel:96-112):
        - model implements PersistentModel → user-managed save, store manifest
        - else → return model itself for automatic blob serialization
          (controller.persistent.serialize_models handles non-picklable
          models by degrading to retrain-on-deploy)."""
        save = getattr(model, "save", None)
        if callable(save) and getattr(model, "PERSISTENT", False):
            if save(model_id, params):
                return PersistentModelManifest(
                    class_name=type(model).__module__ + "." + type(model).__qualname__
                )
        return model


class BaseServing(Generic[Q, P]):
    """Reference BaseServing.scala:28-51."""

    def supplement(self, query: Q) -> Q:
        return query

    def serve(self, query: Q, predictions: Sequence[P]) -> P:
        raise NotImplementedError


class BaseEvaluatorResult:
    """Reference BaseEvaluator.scala:55-72."""

    no_save: bool = False

    def to_one_liner(self) -> str:
        return ""

    def to_html(self) -> str:
        return ""

    def to_json(self) -> str:
        return ""


class BaseEvaluator(Generic[EI, Q, P, A, R]):
    """Reference BaseEvaluator.scala:36-53."""

    def evaluate(
        self,
        ctx: RuntimeContext,
        evaluation: Any,
        engine_eval_data_set: list[
            tuple[Any, list[tuple[EI, list[tuple[Q, P, A]]]]]
        ],
        params: WorkflowParams,
    ) -> R:
        raise NotImplementedError


class BaseEngine(Generic[EI, Q, P, A]):
    """Reference BaseEngine.scala:35-100."""

    def train(self, ctx: RuntimeContext, engine_params: Any) -> list[Any]:
        raise NotImplementedError

    def eval(
        self, ctx: RuntimeContext, engine_params: Any
    ) -> list[tuple[EI, list[tuple[Q, P, A]]]]:
        """Workflow settings come from ctx.workflow_params (single source;
        the reference threads a separate WorkflowParams — BaseEngine.scala:62)."""
        raise NotImplementedError

    def batch_eval(
        self,
        ctx: RuntimeContext,
        engine_params_list: Sequence[Any],
        fold_indices: Optional[Sequence[int]] = None,
    ) -> list[tuple[Any, list[tuple[EI, list[tuple[Q, P, A]]]]]]:
        """Default: map `eval` over the params grid (reference
        BaseEngine.batchEval:81). FastEvalEngine overrides with prefix
        memoization. `fold_indices` restricts the evaluation to a subset
        of the datasource's eval sets (fleet eval shards, ISSUE 20) —
        only forwarded when set, so eval() overrides without the
        parameter keep working on the full-run path."""
        if fold_indices is None:
            return [(ep, self.eval(ctx, ep)) for ep in engine_params_list]
        return [
            (ep, self.eval(ctx, ep, fold_indices=fold_indices))
            for ep in engine_params_list
        ]

    def params_from_variant_json(self, variant: dict) -> Any:
        raise NotImplementedError
