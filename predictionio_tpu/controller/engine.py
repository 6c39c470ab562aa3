"""Engine: binds DASE class maps, concrete train/eval/deploy-rehydration.

Reference controller/Engine.scala (829 LoC): class:80, train:154,
prepareDeploy:196, makeSerializableModels:283, eval:312,
jValueToEngineParams:354, object impls Engine.train:622 / Engine.eval:727;
EngineParams.scala:32,86; SimpleEngine:127; EngineFactory.scala:28.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field as dc_field
from typing import Any, Mapping, Optional, Sequence, Union

from predictionio_tpu.controller.params import (
    EmptyParams,
    ParamsError,
    extract_params,
    params_class_of,
)
from predictionio_tpu.controller.persistent import (
    RetrainOnDeploy,
    load_persistent_model,
)
from predictionio_tpu.core.base import (
    BaseEngine,
    PersistentModelManifest,
    RuntimeContext,
    SanityCheck,
    StopAfterPrepareInterruption,
    StopAfterReadInterruption,
    WorkflowParams,
    doer,
)

log = logging.getLogger(__name__)

# a stage binding: one class, or a name → class map (multi-variant stages)
ClassMap = Union[type, Mapping[str, type]]


@dataclass(frozen=True)
class EngineParams:
    """Named (stage-name, params) per stage + algorithm list (reference
    EngineParams.scala:32)."""

    data_source_params: tuple[str, Any] = ("", EmptyParams())
    preparator_params: tuple[str, Any] = ("", EmptyParams())
    algorithm_params_list: tuple[tuple[str, Any], ...] = ()
    serving_params: tuple[str, Any] = ("", EmptyParams())

    def copy(self, **kw) -> "EngineParams":
        from dataclasses import replace

        return replace(self, **kw)


def _select_folds(eval_sets, fold_indices: Optional[Sequence[int]]):
    """Restrict eval sets to the requested fold indices (fleet eval
    shards, ISSUE 20); identity when unset. Out-of-range folds are a
    spec error, not a silent empty evaluation."""
    if fold_indices is None:
        return eval_sets
    sets = list(eval_sets)
    want = sorted({int(i) for i in fold_indices})
    bad = [i for i in want if i < 0 or i >= len(sets)]
    if bad:
        raise ValueError(
            f"fold_indices {bad} out of range: datasource yields "
            f"{len(sets)} eval set(s)"
        )
    return [sets[i] for i in want]


def _as_classmap(cm: ClassMap) -> Mapping[str, type]:
    if isinstance(cm, Mapping):
        return cm
    return {"": cm}


def _sanity(obj: Any, what: str, wp: WorkflowParams) -> None:
    if wp.skip_sanity_check:
        return
    if isinstance(obj, SanityCheck):
        log.info("sanity check %s", what)
        obj.sanity_check()


def train_stage_histogram():
    """train_stage_seconds{stage} on the process-default registry — any
    server in this process (or `pio metrics`) exposes it on scrape. The
    single declaration point: workflow/core.py records 'persist' through
    this too, so name/labels can never drift apart."""
    from predictionio_tpu.obs import get_default_registry

    return get_default_registry().histogram(
        "train_stage_seconds",
        "train workflow stage durations (read/prepare/train/persist)",
        ("stage",),  # label-bound: literal DASE stage names
    )


def _bridge_train_stage_spans() -> None:
    """The train-stage SPANS are the single timing source (ISSUE 2):
    their durations feed train_stage_seconds{stage} through the span
    recorder's metric bridge — one observation per stage per train, same
    count the direct observe used to produce, but now the trace and the
    histogram can never disagree."""
    from predictionio_tpu.obs.spans import get_default_recorder

    recorder = get_default_recorder()
    for stage in ("read", "prepare", "train", "persist"):
        recorder.bridge(
            f"train.{stage}",
            lambda sp, _s=stage: train_stage_histogram().observe(
                sp.duration, stage=_s
            ),
        )


_bridge_train_stage_spans()


def _stage_span(name: str, **attrs):
    """A span that also snapshots jaxmon's compile counters AND the
    device-profile registry across the stage, attributing XLA
    trace/lower/compile time plus executed FLOPs / HBM bytes / derived
    MFU to the stage that paid them (SURVEY §5: compile cost is the
    train-latency wildcard; ISSUE 3: "the train stage ran at 62% MXU"
    belongs on the stage span, not in hand math)."""
    from contextlib import contextmanager

    from predictionio_tpu.obs import devprof as _devprof
    from predictionio_tpu.obs import spans as _spans
    from predictionio_tpu.obs.jaxmon import compile_snapshot

    @contextmanager
    def cm():
        c0, s0 = compile_snapshot()
        p0 = _devprof.snapshot()
        with _spans.span(name, **attrs) as sp:
            try:
                yield sp
            finally:
                c1, s1 = compile_snapshot()
                if c1 > c0 or s1 > s0:
                    sp.attrs["jit_compiles"] = c1 - c0
                    sp.attrs["jit_compile_sec"] = round(s1 - s0, 4)
                p1 = _devprof.snapshot()
                d_flops = p1.flops - p0.flops
                d_bytes = p1.bytes - p0.bytes
                d_secs = p1.device_seconds - p0.device_seconds
                if d_flops > 0 or d_secs > 0:
                    sp.attrs["device_flops"] = d_flops
                    sp.attrs["device_bytes"] = d_bytes
                    sp.attrs["device_seconds"] = round(d_secs, 4)
                    u = _devprof.mfu(d_flops, d_secs)
                    if u is not None:
                        sp.attrs["mfu"] = round(u, 6)
                    h = _devprof.hbm_fraction(d_bytes, d_secs)
                    if h is not None:
                        sp.attrs["hbm_fraction_of_roof"] = round(h, 6)

    return cm()


class Engine(BaseEngine):
    """Binds named class maps for DataSource/Preparator/Algorithms/Serving
    (reference Engine.scala:80)."""

    def __init__(
        self,
        data_source_classmap: ClassMap,
        preparator_classmap: ClassMap,
        algorithm_classmap: ClassMap,
        serving_classmap: ClassMap,
    ):
        self.data_source_classmap = _as_classmap(data_source_classmap)
        self.preparator_classmap = _as_classmap(preparator_classmap)
        self.algorithm_classmap = _as_classmap(algorithm_classmap)
        self.serving_classmap = _as_classmap(serving_classmap)

    # -- stage instantiation ----------------------------------------------
    def _stage_class(self, cm: Mapping[str, type], name: str, stage: str) -> type:
        if name in cm:
            return cm[name]
        raise ParamsError(
            f"{stage} class {name!r} not bound in engine "
            f"(available: {sorted(cm)})"
        )

    def make_data_source(self, ep: EngineParams):
        name, params = ep.data_source_params
        return doer(self._stage_class(self.data_source_classmap, name, "datasource"), params)

    def make_preparator(self, ep: EngineParams):
        name, params = ep.preparator_params
        return doer(self._stage_class(self.preparator_classmap, name, "preparator"), params)

    def make_algorithms(self, ep: EngineParams) -> list[Any]:
        return [
            doer(self._stage_class(self.algorithm_classmap, name, "algorithm"), params)
            for name, params in ep.algorithm_params_list
        ]

    def make_serving(self, ep: EngineParams):
        name, params = ep.serving_params
        return doer(self._stage_class(self.serving_classmap, name, "serving"), params)

    # -- train (reference Engine.train:154 + object Engine.train:622) ------
    def train(self, ctx: RuntimeContext, engine_params: EngineParams) -> list[Any]:
        # stage timings come FROM the spans (ISSUE 2): run_train's span
        # collector feeds the EngineInstance row snapshot, the bridge
        # declared at module import feeds train_stage_seconds{stage},
        # and the spans themselves land in /debug/traces — one
        # measurement, three views
        wp = ctx.workflow_params
        with _stage_span("train.read") as sp:
            data_source = self.make_data_source(engine_params)
            sp.attrs["datasource"] = type(data_source).__name__
            td = data_source.read_training(ctx)
            _sanity(td, "training data", wp)
        if wp.stop_after_read:
            raise StopAfterReadInterruption()

        with _stage_span("train.prepare") as sp:
            preparator = self.make_preparator(engine_params)
            sp.attrs["preparator"] = type(preparator).__name__
            pd = preparator.prepare(ctx, td)
            _sanity(pd, "prepared data", wp)
        if wp.stop_after_prepare:
            raise StopAfterPrepareInterruption()

        with _stage_span("train.train"):
            algorithms = self.make_algorithms(engine_params)
            if not algorithms:
                raise ParamsError("engine has no algorithms configured")
            models = []
            for i, algo in enumerate(algorithms):
                with _stage_span(
                    "train.algorithm", index=i,
                    algorithm=type(algo).__name__,
                ):
                    model = algo.train(ctx, pd)
                _sanity(model, f"model of algorithm #{i}", wp)
                models.append(model)
        return models

    # -- serializable models (reference makeSerializableModels:283) --------
    def make_serializable_models(
        self,
        ctx: RuntimeContext,
        models: list[Any],
        engine_params: EngineParams,
        instance_id: str,
    ) -> list[Any]:
        algorithms = self.make_algorithms(engine_params)
        return [
            algo.make_persistent_model(
                f"{instance_id}-{i}", model, engine_params.algorithm_params_list[i][1]
            )
            for i, (algo, model) in enumerate(zip(algorithms, models))
        ]

    # -- deploy-time re-hydration (reference prepareDeploy:196) ------------
    def prepare_deploy(
        self,
        ctx: RuntimeContext,
        engine_params: EngineParams,
        persisted_models: list[Any],
        instance_id: str = "deploy",
    ) -> list[Any]:
        algorithms = self.make_algorithms(engine_params)
        if len(persisted_models) != len(algorithms):
            raise ParamsError(
                f"persisted model count {len(persisted_models)} != "
                f"algorithm count {len(algorithms)}"
            )
        needs_retrain = any(
            isinstance(m, RetrainOnDeploy) or m is None for m in persisted_models
        )
        retrained: Optional[list[Any]] = None
        if needs_retrain:
            log.info("some models require retrain-on-deploy; running train")
            retrained = self.train(ctx, engine_params)
        out = []
        for i, m in enumerate(persisted_models):
            if isinstance(m, PersistentModelManifest):
                out.append(
                    load_persistent_model(
                        m,
                        f"{instance_id}-{i}",
                        engine_params.algorithm_params_list[i][1],
                    )
                )
            elif isinstance(m, RetrainOnDeploy) or m is None:
                assert retrained is not None
                out.append(retrained[i])
            else:
                out.append(m)
        return out

    # -- eval (reference Engine.eval:312 + object Engine.eval:727) ---------
    def eval(
        self,
        ctx: RuntimeContext,
        engine_params: EngineParams,
        fold_indices: Optional[Sequence[int]] = None,
    ) -> list[Any]:
        data_source = self.make_data_source(engine_params)
        preparator = self.make_preparator(engine_params)
        algorithms = self.make_algorithms(engine_params)
        serving = self.make_serving(engine_params)
        eval_sets = _select_folds(data_source.read_eval(ctx), fold_indices)
        results = []
        for td, ei, qa in eval_sets:
            pd = preparator.prepare(ctx, td)
            models = [algo.train(ctx, pd) for algo in algorithms]
            supplemented = [
                (qx, serving.supplement(q)) for qx, (q, _a) in enumerate(qa)
            ]
            # per-algo batch predict, regrouped per query (reference
            # Engine.scala:770-811 union → groupByKey → serve)
            per_algo: list[dict[int, Any]] = []
            for algo, model in zip(algorithms, models):
                preds = algo.batch_predict(ctx, model, supplemented)
                per_algo.append(dict(preds))
            qpa = []
            for qx, (q, a) in enumerate(qa):
                predictions = [pa[qx] for pa in per_algo]
                p = serving.serve(q, predictions)
                qpa.append((q, p, a))
            results.append((ei, qpa))
        return results

    # -- grid-batched tuning (VERDICT r2 #9; beats the reference's strictly
    # serial Engine.eval grid, Engine.scala:758-764) ------------------------
    def batch_eval(
        self,
        ctx: RuntimeContext,
        engine_params_list,
        fold_indices: Optional[Sequence[int]] = None,
    ):
        eps = list(engine_params_list)
        if self._grid_batchable(ctx, eps):
            return self._batch_eval_grid(ctx, eps, fold_indices=fold_indices)
        return super().batch_eval(ctx, eps, fold_indices=fold_indices)

    def _grid_batchable(self, ctx: RuntimeContext, eps: list) -> bool:
        """True when the grid varies ONLY in a single algorithm's
        hyperparams and that algorithm implements train_grid — then every
        fold trains all grid points in one device program. Mesh evals stay
        serial: the grid kernels are single-device (the per-point train
        path carries the sharding)."""
        if len(eps) < 2 or getattr(ctx, "mesh", None) is not None:
            return False
        if any(len(ep.algorithm_params_list) != 1 for ep in eps):
            return False
        if len({ep.algorithm_params_list[0][0] for ep in eps}) != 1:
            return False
        algo = self.make_algorithms(eps[0])[0]
        if not callable(getattr(algo, "train_grid", None)):
            return False
        from predictionio_tpu.controller.params import params_to_json

        def shared_key(ep):
            return tuple(
                (name, params_to_json(p))
                for name, p in (
                    ep.data_source_params,
                    ep.preparator_params,
                    ep.serving_params,
                )
            )

        key0 = shared_key(eps[0])
        return all(shared_key(ep) == key0 for ep in eps[1:])

    def _batch_eval_grid(
        self,
        ctx: RuntimeContext,
        eps: list,
        fold_indices: Optional[Sequence[int]] = None,
    ):
        ep0 = eps[0]
        data_source = self.make_data_source(ep0)
        preparator = self.make_preparator(ep0)
        serving = self.make_serving(ep0)
        algos = [self.make_algorithms(ep)[0] for ep in eps]
        params_list = [ep.algorithm_params_list[0][1] for ep in eps]
        eval_sets = _select_folds(
            list(data_source.read_eval(ctx)), fold_indices  # may be a generator
        )
        per_ep: list[list] = [[] for _ in eps]
        for td, ei, qa in eval_sets:
            pd = preparator.prepare(ctx, td)
            models = algos[0].train_grid(ctx, pd, params_list)
            supplemented = [
                (qx, serving.supplement(q)) for qx, (q, _a) in enumerate(qa)
            ]
            for i, model in enumerate(models):
                preds = dict(algos[i].batch_predict(ctx, model, supplemented))
                qpa = [
                    (q, serving.serve(q, [preds[qx]]), a)
                    for qx, (q, a) in enumerate(qa)
                ]
                per_ep[i].append((ei, qpa))
        log.info(
            "grid-batched eval: %d points x %d folds trained as %d device "
            "programs", len(eps), len(eval_sets), len(eval_sets),
        )
        return list(zip(eps, per_ep))

    # -- engine.json → EngineParams (reference jValueToEngineParams:354) ---
    @staticmethod
    def _resolve_stage_class(
        cm: Mapping[str, type], name: str, what: str
    ) -> type:
        """Name → class with the single-binding fallback: an unnamed stage
        resolves to the sole bound class."""
        cls = cm.get(name)
        if cls is None and name == "" and len(cm) == 1:
            cls = next(iter(cm.values()))
        if cls is None:
            raise ParamsError(
                f"variant {what} names {name!r}, not bound "
                f"(available: {sorted(cm)})"
            )
        return cls

    def params_from_variant_json(self, variant: dict) -> EngineParams:
        def stage(key: str, cm: Mapping[str, type]) -> tuple[str, Any]:
            obj = variant.get(key)
            if obj is None:
                name, raw = "", None
            else:
                name = obj.get("name", "")
                raw = obj.get("params")
            cls = self._resolve_stage_class(cm, name, key)
            return name, extract_params(params_class_of(cls), raw)

        ds = stage("datasource", self.data_source_classmap)
        prep = stage("preparator", self.preparator_classmap)
        serv = stage("serving", self.serving_classmap)

        algo_list = []
        for obj in variant.get("algorithms", []):
            name = obj.get("name", "")
            cls = self._resolve_stage_class(
                self.algorithm_classmap, name, "algorithm"
            )
            algo_list.append(
                (name, extract_params(params_class_of(cls), obj.get("params")))
            )
        if not algo_list:
            # default: single bound algorithm with default params
            if len(self.algorithm_classmap) == 1:
                name, cls = next(iter(self.algorithm_classmap.items()))
                algo_list = [(name, extract_params(params_class_of(cls), None))]
        return EngineParams(
            data_source_params=ds,
            preparator_params=prep,
            algorithm_params_list=tuple(algo_list),
            serving_params=serv,
        )


class SimpleEngine(Engine):
    """Single-algorithm engine with identity prep + first serving
    (reference EngineParams.scala SimpleEngine:127)."""

    def __init__(self, data_source_class: type, algorithm_class: type):
        from predictionio_tpu.controller.dase import FirstServing, IdentityPreparator

        super().__init__(
            data_source_class, IdentityPreparator, algorithm_class, FirstServing
        )


class EngineFactory:
    """Subclass with `apply()` returning an Engine (reference
    EngineFactory.scala:28); engine.json's engineFactory names it."""

    def apply(self) -> BaseEngine:
        raise NotImplementedError


def resolve_engine(factory: Any) -> BaseEngine:
    """Accept an Engine, an EngineFactory class/instance, or a callable
    returning an Engine (reference WorkflowUtils.getEngine:62 handles
    object-vs-class duality)."""
    if isinstance(factory, BaseEngine):
        return factory
    if isinstance(factory, type):
        factory = factory()
    if isinstance(factory, EngineFactory):
        return factory.apply()
    if callable(factory):
        result = factory()
        if isinstance(result, BaseEngine):
            return result
    raise ParamsError(f"cannot resolve an Engine from {factory!r}")
