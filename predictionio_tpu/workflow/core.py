"""Train workflow driver: variant JSON → engine → models → MODELDATA.

Reference: CreateWorkflow.main (CreateWorkflow.scala:133) +
CoreWorkflow.runTrain (CoreWorkflow.scala:42-99). The Spark driver process
becomes a plain function call (the CLI spawns it in-process or as a child
python, not via spark-submit); the SparkContext becomes a RuntimeContext
carrying the storage registry and an optional device mesh built from the
variant's `mesh` config (the re-design of `sparkConf` pass-through,
WorkflowUtils.extractSparkConf:316).
"""

from __future__ import annotations

import datetime as _dt
import json
import logging
import uuid
from typing import Any, Optional

from predictionio_tpu.controller.engine import EngineParams, resolve_engine
from predictionio_tpu.controller.params import load_symbol, params_to_json
from predictionio_tpu.controller.persistent import serialize_models
from predictionio_tpu.core.base import (
    RuntimeContext,
    StopAfterPrepareInterruption,
    StopAfterReadInterruption,
    WorkflowParams,
)
from predictionio_tpu.data.storage.base import EngineInstance, Model
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.obs import get_default_registry
from predictionio_tpu.obs import spans as _spans

log = logging.getLogger(__name__)

#: span name -> key in EngineInstance.env["stage_timings"]; every other
#: span of a train job stands there under its own name
_STAGE_KEYS = {
    "train.read": "read", "train.prepare": "prepare",
    "train.train": "train", "train.persist": "persist", "train": "job",
}


def _stage_json(stage: tuple[str, Any]) -> str:
    """Persist a (stage-name, params) pair — the name matters: deploy must
    rebind the same named class the train run used (the reference stores
    name+params per stage, EngineInstances.scala:43)."""
    name, params = stage
    return json.dumps(
        {"name": name, "params": json.loads(params_to_json(params))},
        sort_keys=True,
    )


def load_variant(path: str) -> dict:
    """Load an engine variant JSON file (engine.json)."""
    with open(path) as f:
        variant = json.load(f)
    for key in ("id", "engineFactory"):
        if key not in variant:
            raise ValueError(f"engine variant is missing {key!r} ({path})")
    return variant


def device_profile() -> dict:
    """Where this process's device work ran, from the profiler registry:
    platform / device_kind / device_count as jax reports them (None in a
    process that never loaded jax) and, per executed executable, the
    static kwargs it ran with plus its compile and device seconds.
    `pio train` records it on the EngineInstance row, `pio deploy`
    prints it after warm-up; `GET /debug/profile` is the live view of
    the same registry."""
    from predictionio_tpu.obs import devprof

    report = devprof.report()
    keep = (
        "static_kwargs", "invocations", "compile_seconds", "device_seconds",
    )
    return {
        **{
            k: report["platform"][k]
            for k in ("platform", "device_kind", "device_count")
        },
        "executables": {
            row["name"]: {k: row[k] for k in keep}
            for row in report["executables"]
        },
    }


def format_device_profile(profile: dict) -> list[str]:
    """The `[INFO]` lines `pio train` / `pio deploy` print."""
    lines = [
        f"Device: platform={profile['platform']} "
        f"device_kind={profile['device_kind']} "
        f"device_count={profile['device_count']}"
    ]
    for name, row in sorted(profile["executables"].items()):
        shown = ", ".join(
            f"{k}={v}" for k, v in sorted(row["static_kwargs"].items())
            if k in ("mode", "pallas_mode", "dense_dtype", "mesh")
        )
        lines.append(
            f"Executable: {name}({shown}) x{row['invocations']} "
            f"compile {row['compile_seconds']}s "
            f"device {row['device_seconds']}s"
        )
    return lines


def runtime_context_from_variant(
    storage: Storage,
    variant: dict,
    mode: str = "train",
    workflow_params: Optional[WorkflowParams] = None,
    use_mesh: bool = True,
) -> RuntimeContext:
    mesh = None
    if use_mesh and variant.get("mesh"):
        from predictionio_tpu.parallel.mesh import MeshConf

        mesh = MeshConf.from_json(variant["mesh"]).build()
    return RuntimeContext(
        storage=storage,
        mesh=mesh,
        mode=mode,
        workflow_params=workflow_params or WorkflowParams(),
    )


def run_train(
    storage: Storage,
    variant: dict,
    workflow_params: Optional[WorkflowParams] = None,
    engine_params: Optional[EngineParams] = None,
    engine_id: Optional[str] = None,
    engine_version: str = "0",
) -> EngineInstance:
    """The whole `pio train` data path (reference call stack SURVEY.md §3.1):
    resolve factory → params from variant → EngineInstance INIT row →
    engine.train → serializable models → MODELDATA blob → COMPLETED.

    Returns the COMPLETED EngineInstance row.
    """
    wp = workflow_params or WorkflowParams()
    from predictionio_tpu.obs.jaxmon import ensure_compile_listener

    ensure_compile_listener()  # count this run's jit compiles on scrape
    engine = resolve_engine(load_symbol(variant["engineFactory"]))
    if engine_params is None:
        engine_params = engine.params_from_variant_json(variant)

    instances = storage.get_meta_data_engine_instances()
    now = _dt.datetime.now(_dt.timezone.utc)
    instance = EngineInstance(
        id=str(uuid.uuid4()),
        status="INIT",
        start_time=now,
        end_time=now,
        engine_id=engine_id or variant["id"],
        engine_version=engine_version,
        engine_variant=variant["id"],
        engine_factory=variant["engineFactory"],
        batch=wp.batch,
        data_source_params=_stage_json(engine_params.data_source_params),
        preparator_params=_stage_json(engine_params.preparator_params),
        algorithms_params=json.dumps(
            [
                {"name": name, "params": json.loads(params_to_json(p))}
                for name, p in engine_params.algorithm_params_list
            ]
        ),
        serving_params=_stage_json(engine_params.serving_params),
        mesh_conf=variant.get("mesh") or {},
    )
    import contextlib

    profile_cm: Any = contextlib.nullcontext()
    if wp.profile_dir:
        # SURVEY §5: XLA profiler hook — the whole train runs under a
        # jax.profiler trace; inspect with tensorboard/xprof. Built BEFORE
        # the instance row is inserted so a failure here can't strand a
        # row in INIT.
        import jax

        profile_cm = jax.profiler.trace(wp.profile_dir)

    instance_id = instances.insert(instance)
    instance.id = instance_id

    ctx = runtime_context_from_variant(storage, variant, "train", wp)
    ctx.instance_id = instance_id

    def _record_timings(where_it_ran: bool = True) -> None:
        # the EngineInstance blob stays as a point-in-time snapshot of
        # what the unified registry recorded live (ISSUE 1): every span
        # that completed under this job, name -> seconds (ISSUE 25); the
        # four DASE stages and the job's root span keep the short keys
        # `pio status` and deploy/registry.py read
        timings = {
            _STAGE_KEYS.get(name, name): round(seconds, 4)
            for name, seconds in collected.seconds.items()
        }
        timings["unattributed"] = round(collected.unattributed, 4)
        instance.env = dict(instance.env or {})
        instance.env["stage_timings"] = json.dumps(timings)
        if where_it_ran:
            # ... and of WHERE it ran: the device as jax reports it and
            # the executables this process has run, with their static
            # kwargs (which ALS path, which storage dtype, which mode)
            instance.env["device_profile"] = json.dumps(device_profile())

    def _count_run(status: str) -> None:
        get_default_registry().counter(
            "train_runs_total", "train workflows by final status",
            ("status",),  # label-bound: literal status set
        ).inc(status=status)

    with _spans.collect() as collected:
        try:
            # root span of the whole train (ISSUE 2): opens a trace if the
            # caller didn't (CLI `pio train`), parents every DASE stage span
            # engine.train emits, and — because an aborted train marks it
            # errored — guarantees tail sampling retains failed runs
            with _spans.span(
                "train", server="train", instance_id=instance_id,
                engine=instance.engine_id, variant=instance.engine_variant,
            ):
                instance.status = "TRAINING"
                instances.update(instance)
                with profile_cm:
                    try:
                        models = engine.train(ctx, engine_params)
                    except (
                        StopAfterReadInterruption, StopAfterPrepareInterruption
                    ) as e:
                        # intentional debug stop-points, not failures
                        # (reference CoreWorkflow.scala:88-93 logs
                        # "Training interrupted")
                        log.info("training interrupted by %s", type(e).__name__)
                        instance.status = "INTERRUPTED"
                        instance.end_time = _dt.datetime.now(_dt.timezone.utc)
                        _record_timings()
                        _count_run("INTERRUPTED")
                        instances.update(instance)
                        return instance
                    if wp.save_model:
                        from predictionio_tpu.controller.engine import (
                            _stage_span,
                        )

                        # the histogram observation comes from the span via
                        # the bridge in controller/engine.py
                        with _stage_span("train.persist"):
                            serializable = engine.make_serializable_models(
                                ctx, models, engine_params, instance_id
                            )
                            with _spans.span("persist.serialize") as sp:
                                blob = serialize_models(serializable, sp.attrs)
                            with _spans.span("persist.write"):
                                storage.get_model_data_models().insert(
                                    Model(id=instance_id, models=blob)
                                )
            instance.status = "COMPLETED"
            instance.end_time = _dt.datetime.now(_dt.timezone.utc)
            _record_timings()
            _count_run("COMPLETED")
            instances.update(instance)
            _register_manifest(storage, instance, variant)
            log.info(
                "training completed: instance %s (stages: %s)",
                instance_id, instance.env["stage_timings"],
            )
            return instance
        except Exception:
            instance.status = "ABORTED"
            instance.end_time = _dt.datetime.now(_dt.timezone.utc)
            # partial timings show WHERE the failed run spent time; the
            # device is not asked again here — if it is what failed, asking
            # would raise over the error being reported
            _record_timings(where_it_ran=False)
            _count_run("ABORTED")
            instances.update(instance)
            raise


def _register_manifest(
    storage: Storage, instance: EngineInstance, variant: dict
) -> None:
    """Upsert the EngineManifest row for a successfully trained engine.

    Reference RegisterEngine.scala:32 writes the manifest at `pio build`;
    here there is no build step (engines are Python entry points named in
    engine.json), so registration happens at the first successful train —
    the moment the factory provably resolves and runs. `pio status` lists
    the registered engines."""
    from predictionio_tpu.data.storage.base import EngineManifest

    try:
        factory = load_symbol(instance.engine_factory)
        description = (factory.__doc__ or "").strip().splitlines()
        storage.get_meta_data_engine_manifests().update(
            EngineManifest(
                id=instance.engine_id,
                version=instance.engine_version,
                name=variant.get("id", instance.engine_id),
                description=description[0] if description else None,
                files=(instance.engine_factory.rsplit(".", 1)[0],),
                engine_factory=instance.engine_factory,
            ),
            upsert=True,
        )
    except Exception:
        log.exception("engine manifest registration failed (non-fatal)")


def prepare_deploy_models(
    storage: Storage,
    instance: EngineInstance,
    engine: Any = None,
    engine_params: Optional[EngineParams] = None,
    use_mesh: bool = True,
) -> tuple[Any, EngineParams, list[Any]]:
    """Re-hydrate a COMPLETED instance's models for serving (reference
    CreateServer.createServerActorWithEngine:206 → Engine.prepareDeploy:196).

    When `use_mesh` and the train run recorded a mesh config, the deploy
    context rebuilds it — so retrain-on-deploy models retrain with the
    same sharding the train run used.

    Returns (engine, engine_params, models)."""
    if engine is None:
        engine = resolve_engine(load_symbol(instance.engine_factory))
    if engine_params is None:
        engine_params = engine_instance_to_engine_params(engine, instance)
    blob = storage.get_model_data_models().get(instance.id)
    if blob is None:
        raise RuntimeError(f"no model blob stored for instance {instance.id}")
    from predictionio_tpu.controller.persistent import deserialize_models

    persisted = deserialize_models(blob.models)
    mesh = None
    if use_mesh and instance.mesh_conf:
        from predictionio_tpu.parallel.mesh import MeshConf

        mesh = MeshConf.from_json(instance.mesh_conf).build()
    ctx = RuntimeContext(storage=storage, mesh=mesh, mode="serve")
    models = engine.prepare_deploy(
        ctx, engine_params, persisted, instance_id=instance.id
    )
    return engine, engine_params, models


def _stage_from_json(raw: str) -> Optional[dict]:
    """Invert _stage_json → a variant stage object, or None when empty."""
    if not raw or raw == "{}":
        return None
    obj = json.loads(raw)
    if "name" not in obj:  # legacy bare-params form
        return {"params": obj} if obj else None
    if not obj["name"] and not obj.get("params"):
        return None
    return {"name": obj["name"], "params": obj.get("params") or None}


def engine_instance_to_engine_params(engine: Any, instance: EngineInstance) -> EngineParams:
    """Rebuild EngineParams from the name+params JSON recorded on the
    instance row (reference Engine.engineInstanceToEngineParams:419)."""
    variant = {
        "id": instance.engine_variant,
        "engineFactory": instance.engine_factory,
    }
    for key, raw in (
        ("datasource", instance.data_source_params),
        ("preparator", instance.preparator_params),
        ("serving", instance.serving_params),
    ):
        stage = _stage_from_json(raw)
        if stage is not None:
            variant[key] = stage
    if instance.algorithms_params:
        variant["algorithms"] = json.loads(instance.algorithms_params)
    return engine.params_from_variant_json(variant)
