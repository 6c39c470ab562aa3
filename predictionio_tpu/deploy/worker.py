"""Train-job subprocess entry point (`python -m predictionio_tpu.deploy.worker`).

The scheduler writes a spec file (storage wiring + variant + result
path), spawns this module, and supervises from outside. In here the job
is plain: open the same stores, run the full `run_train` data path,
register the COMPLETED instance as a model version, write the result
receipt, exit 0.

Exit codes are the scheduler's retry contract:
- 0                  — trained + registered
- EXIT_TRAIN_FAILED  — the train itself raised / did not complete
                       (deterministic; the scheduler fails the job fast)
- anything else      — infra trouble (storage down, import error, OOM
                       kill); the scheduler re-queues with backoff
"""

from __future__ import annotations

import json
import logging
import sys
import traceback


def main(argv: list[str]) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    if len(argv) != 2:
        print("usage: python -m predictionio_tpu.deploy.worker <spec.json>",
              file=sys.stderr)
        return 2
    from predictionio_tpu.data.storage.base import StorageError
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.deploy.registry import ModelRegistry
    from predictionio_tpu.deploy.scheduler import (
        EXIT_INFRA_FAILED,
        EXIT_TRAIN_FAILED,
        storage_config_from_json,
    )
    from predictionio_tpu.workflow.core import run_train

    from predictionio_tpu.utils.jaxenv import ensure_compile_cache

    ensure_compile_cache()
    with open(argv[1]) as f:
        spec = json.load(f)
    storage = Storage(storage_config_from_json(spec["storage"]))

    # push telemetry (ISSUE 17): this process usually dies before any
    # scraper gets a chance to poll it, so its train spans / stage
    # metrics / devprof report ship OUT instead — spooled durably every
    # interval, flushed on exit (atexit covers clean exits AND the
    # uncaught-exception path; kill -9 leaves the spool for the
    # supervisor to ship). No-op unless PIO_PUSH_URL/PIO_PUSH_SPOOL set.
    shipper = None
    try:
        from predictionio_tpu.obs.monitor.push import TelemetryShipper

        shipper = TelemetryShipper.from_env(job_id=spec.get("job_id"))
        if shipper is not None:
            shipper.start()
            import atexit

            atexit.register(shipper.stop)
    except Exception:
        logging.getLogger(__name__).debug(
            "telemetry shipper unavailable", exc_info=True
        )

    # retried-job adoption (ISSUE 9 satellite): if a previous attempt of
    # THIS job already trained and registered a version — and only the
    # result receipt / bookkeeping was lost — adopt it instead of paying
    # a full duplicate train. The job id is stamped on every version
    # this worker registers (below), so the check is one registry fold.
    job_id = spec.get("job_id")
    if job_id:
        try:
            existing = ModelRegistry(storage).find_by_job(job_id)
        except Exception:
            existing = None  # storage hiccup: fall through to training
        if existing is not None and existing.status not in (
            "rolled_back", "archived"
        ):
            with open(spec["result_path"], "w") as f:
                json.dump({
                    "instance_id": existing.instance_id,
                    "model_version": existing.id,
                }, f)
            print(
                f"job {job_id}: adopting already-registered version "
                f"{existing.id} (instance {existing.instance_id}); "
                f"skipping retrain"
            )
            return 0
    try:
        instance = run_train(
            storage, spec["variant"], engine_id=spec.get("engine_id")
        )
    except StorageError:
        traceback.print_exc()
        return EXIT_INFRA_FAILED
    except Exception:
        traceback.print_exc()
        return EXIT_TRAIN_FAILED
    if instance.status != "COMPLETED":
        print(f"train ended {instance.status}, not COMPLETED",
              file=sys.stderr)
        return EXIT_TRAIN_FAILED

    devprof_snapshot: dict = {}
    try:
        from predictionio_tpu.obs import devprof as _devprof

        report = _devprof.report()
        if report.get("executables"):
            devprof_snapshot = report
    except Exception:
        pass  # profiling is best-effort; the version record stays valid

    version = ModelRegistry(storage).register(
        instance, devprof=devprof_snapshot, job_id=job_id,
    )
    with open(spec["result_path"], "w") as f:
        json.dump(
            {"instance_id": instance.id, "model_version": version.id}, f
        )
    print(f"trained instance {instance.id} → model version {version.id}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
