"""`pio` console: the operator CLI.

Reference: tools/.../console/Console.scala:131 (scopt dispatch, 1,277 LoC),
App.scala (app/channel mgmt), AccessKey.scala, Export.scala / Import.scala,
RunWorkflow/RunServer (spark-submit assembly — here train/deploy run
in-process; no JVM, no sbt build step: engines are Python entry points
named in engine.json, so `pio build` has no equivalent and engine
registration happens implicitly at train time).

Commands:
  app new|list|show|delete|data-delete; channel new|delete
  accesskey new|list|delete
  train / deploy / eval / eventserver
  status / export / import
  metrics / trace list|show|export|stats / profile list|show|capture
  faults list|set|clear
  jobs submit|list|show|logs|worker / models list|show|promote|rollback|gc
  rollout start|status|abort
"""

from __future__ import annotations

import argparse
import json as _json
import os
import sys
import time
from typing import Optional

from predictionio_tpu.data.storage.base import App, Channel
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.tools import common
from predictionio_tpu.tools.common import CommandError
from predictionio_tpu.utils.env import env_str as _env_str


def _storage() -> Storage:
    return Storage.get_instance()


def _fail(msg: str) -> int:
    print(f"[ERROR] {msg}", file=sys.stderr)
    return 1


def _get_app(storage: Storage, name: str) -> Optional[App]:
    app = storage.get_meta_data_apps().get_by_name(name)
    if app is None:
        print(f"[ERROR] App '{name}' does not exist.", file=sys.stderr)
    return app


# ---------------------------------------------------------------------------
# app / channel (reference console/App.scala)
# ---------------------------------------------------------------------------


def cmd_app_new(args) -> int:
    app, key = common.create_app(
        _storage(), args.name,
        description=args.description, access_key=args.access_key,
    )
    print(f"[INFO] App created: ID={app.id} Name={app.name}")
    print(f"[INFO] Access key: {key}")
    return 0


def cmd_app_list(args) -> int:
    storage = _storage()
    keys = storage.get_meta_data_access_keys()
    print(f"{'ID':>4}  {'Name':<24} Access key(s)")
    for app in sorted(storage.get_meta_data_apps().get_all(), key=lambda a: a.id):
        ks = ", ".join(k.key for k in keys.get_by_app_id(app.id)) or "-"
        print(f"{app.id:>4}  {app.name:<24} {ks}")
    return 0


def cmd_app_show(args) -> int:
    storage = _storage()
    app = _get_app(storage, args.name)
    if app is None:
        return 1
    print(f"[INFO] App: ID={app.id} Name={app.name} Description={app.description or ''}")
    for ch in storage.get_meta_data_channels().get_by_app_id(app.id):
        print(f"[INFO] Channel: ID={ch.id} Name={ch.name}")
    for k in storage.get_meta_data_access_keys().get_by_app_id(app.id):
        events = ",".join(k.events) or "(all)"
        print(f"[INFO] Access key: {k.key} events={events}")
    return 0


def cmd_app_delete(args) -> int:
    storage = _storage()
    app = _get_app(storage, args.name)
    if app is None:
        return 1
    if not args.force:
        confirm = input(
            f"Delete app '{app.name}' and ALL its data? (YES to confirm): "
        )
        if confirm != "YES":
            print("[INFO] Aborted.")
            return 1
    common.delete_app(storage, app)
    print(f"[INFO] App '{app.name}' deleted.")
    return 0


def cmd_app_data_delete(args) -> int:
    storage = _storage()
    app = _get_app(storage, args.name)
    if app is None:
        return 1
    channel_id = (
        common.resolve_channel(storage, app, args.channel)
        if args.channel
        else None
    )
    if not args.force:
        scope = f"channel '{args.channel}'" if args.channel else "default channel"
        confirm = input(
            f"Delete all event data of app '{app.name}' ({scope})? (YES to confirm): "
        )
        if confirm != "YES":
            print("[INFO] Aborted.")
            return 1
    common.delete_app_data(storage, app, channel_id)
    print(f"[INFO] Event data of app '{app.name}' deleted.")
    return 0


def cmd_channel_new(args) -> int:
    storage = _storage()
    app = _get_app(storage, args.app)
    if app is None:
        return 1
    if not Channel.is_valid_name(args.channel):
        return _fail(f"Channel name {args.channel!r}: {Channel.NAME_CONSTRAINT}")
    chans = storage.get_meta_data_channels()
    if any(c.name == args.channel for c in chans.get_by_app_id(app.id)):
        return _fail(f"Channel '{args.channel}' already exists.")
    ch_id = chans.insert(Channel(id=0, name=args.channel, app_id=app.id))
    storage.get_events().init_app(app.id, ch_id)
    print(f"[INFO] Channel created: ID={ch_id} Name={args.channel}")
    return 0


def cmd_channel_delete(args) -> int:
    storage = _storage()
    app = _get_app(storage, args.app)
    if app is None:
        return 1
    channel_id = common.resolve_channel(storage, app, args.channel)
    storage.get_events().remove_app(app.id, channel_id)
    storage.get_meta_data_channels().delete(channel_id)
    print(f"[INFO] Channel '{args.channel}' deleted.")
    return 0


# ---------------------------------------------------------------------------
# accesskey (reference console/AccessKey.scala)
# ---------------------------------------------------------------------------


def cmd_accesskey_new(args) -> int:
    storage = _storage()
    app = _get_app(storage, args.app)
    if app is None:
        return 1
    events = tuple(e for e in (args.events or "").split(",") if e)
    key = common.create_access_key(storage, app, args.key, events)
    print(f"[INFO] Access key created: {key}")
    return 0


def cmd_accesskey_list(args) -> int:
    storage = _storage()
    keys = storage.get_meta_data_access_keys()
    if args.app:
        app = _get_app(storage, args.app)
        if app is None:
            return 1
        rows = keys.get_by_app_id(app.id)
    else:
        rows = keys.get_all()
    print(f"{'App':>4}  {'Access key':<48} Allowed events")
    for k in rows:
        events = ",".join(k.events) or "(all)"
        print(f"{k.app_id:>4}  {k.key:<48} {events}")
    return 0


def cmd_accesskey_delete(args) -> int:
    if _storage().get_meta_data_access_keys().delete(args.key):
        print(f"[INFO] Access key deleted: {args.key}")
        return 0
    return _fail(f"Access key not found: {args.key}")


# ---------------------------------------------------------------------------
# train / deploy / eval / eventserver (reference RunWorkflow/RunServer)
# ---------------------------------------------------------------------------


def _serve_until_interrupt(server, banner: str) -> int:
    """Start a ServerProcess, print the banner, block until Ctrl-C or
    until the server stops itself (`GET /stop` ends the process — a
    stopped server must not leave its process holding the device)."""
    port = server.start()
    print(banner.format(port=port), flush=True)
    try:
        server.wait()
    except KeyboardInterrupt:
        server.stop()
    return 0


def cmd_train(args) -> int:
    from predictionio_tpu.core.base import WorkflowParams
    from predictionio_tpu.utils.jaxenv import ensure_compile_cache
    from predictionio_tpu.workflow.core import (
        format_device_profile,
        load_variant,
        run_train,
    )

    ensure_compile_cache()
    variant = load_variant(args.engine_json)
    wp = WorkflowParams(
        batch=args.batch or "",
        skip_sanity_check=args.skip_sanity_check,
        stop_after_read=args.stop_after_read,
        stop_after_prepare=args.stop_after_prepare,
        profile_dir=args.profile,
    )
    inst = run_train(
        _storage(), variant, workflow_params=wp,
        engine_version=args.engine_version,
    )
    print(f"[INFO] Training {inst.status.lower()}: instance {inst.id}")
    if args.profile:
        print(f"[INFO] XLA profile written to {args.profile} "
              f"(inspect with tensorboard --logdir)")
    timings = (inst.env or {}).get("stage_timings")
    if timings:
        print(f"[INFO] Stage timings (s): {timings}")
    profile = (inst.env or {}).get("device_profile")
    if profile:
        for line in format_device_profile(_json.loads(profile)):
            print(f"[INFO] {line}")
    return 0 if inst.status in ("COMPLETED", "INTERRUPTED") else 1


def cmd_deploy(args) -> int:
    from predictionio_tpu.utils.jaxenv import ensure_compile_cache
    from predictionio_tpu.workflow.core import (
        device_profile,
        format_device_profile,
        load_variant,
    )
    from predictionio_tpu.workflow.server import (
        QueryServer,
        QueryServerConfig,
        latest_completed_runtime,
    )

    ensure_compile_cache()
    variant = load_variant(args.engine_json)
    runtime = latest_completed_runtime(
        _storage(), variant["id"], args.engine_version, variant["id"]
    )
    # warm-up has run every serving program once: say where, and in
    # which kernel mode, before reporting the engine live
    for line in format_device_profile(device_profile()):
        print(f"[INFO] {line}")
    config = QueryServerConfig(
        ip=args.ip,
        port=args.port,
        feedback=args.feedback,
        event_server_url=args.event_server_url,
        access_key=args.access_key,
        log_url=args.log_url,
    )
    return _serve_until_interrupt(
        QueryServer(_storage(), runtime, config),
        f"[INFO] Engine is deployed and running. Engine API is live at "
        f"http://{args.ip}:{{port}}.",
    )


def _run_legacy_evaluation(target: str, params_generator) -> int:
    from predictionio_tpu.controller.evaluation import Evaluation
    from predictionio_tpu.controller.params import load_symbol
    from predictionio_tpu.workflow.evaluation import run_evaluation

    evaluation = load_symbol(target)
    if isinstance(evaluation, type):
        evaluation = evaluation()
    if not isinstance(evaluation, Evaluation):
        return _fail(f"{target} is not an Evaluation")
    params_list = None
    if params_generator:
        gen = load_symbol(params_generator)
        if isinstance(gen, type):
            gen = gen()
        params_list = list(gen.engine_params_list)
    inst, result = run_evaluation(_storage(), evaluation, params_list)
    print(f"[INFO] Evaluation {inst.status}: {result.to_one_liner()}")
    return 0 if inst.status == "EVALCOMPLETED" else 1


def _local_fleet(storage, n: int) -> list:
    """Spin n in-process FleetMembers so `pio eval run` / `pio tune`
    work without a standing fleet (each member supervises real shard
    subprocesses)."""
    from predictionio_tpu.fleet.coordinator import FleetMember

    members = [FleetMember(storage) for _ in range(max(1, n))]
    for m in members:
        m.start()
    return members


def _print_eval_run(run: dict, points: list) -> None:
    print(f"run        {run['id']}")
    print(f"engine     {run.get('engine_id')}"
          + (f"  tenant {run['tenant']}" if run.get("tenant") else ""))
    print(f"status     {run.get('status')}")
    print(f"metric     {run.get('metric_header')}"
          f" ({'higher' if run.get('higher_is_better', True) else 'lower'}"
          f" is better)")
    if run.get("winner_index") is not None:
        print(f"winner     point {run['winner_index']}"
              f"  score {run.get('winner_score')}")
    if run.get("winner_model_version"):
        print(f"lineage    model version {run['winner_model_version']}")
    if points:
        print(f"{'POINT':>5s}  {'DONE':4s}  {'SCORE':>12s}  PARAMS")
        for p in points:
            score = "-" if p["score"] is None else f"{p['score']:.6g}"
            mark = "yes" if p["complete"] else f"{len(p['folds_done'])}f"
            print(f"{p['point_index']:>5d}  {mark:4s}  {score:>12s}  "
                  f"{_json.dumps(p.get('params') or {})[:80]}")


def cmd_eval(args) -> int:
    action = getattr(args, "eval_action", None)
    if action == "run":
        if not os.path.isfile(args.target):
            return _run_legacy_evaluation(args.target, args.params_generator)
        from predictionio_tpu.evalfleet.driver import EvalDriver
        from predictionio_tpu.evalfleet.specs import EvalSpec

        storage = _storage()
        try:
            spec = EvalSpec.load(args.target)
        except (OSError, ValueError, KeyError) as e:
            return _fail(f"bad eval spec: {e}")
        driver = EvalDriver(storage)
        members = (
            _local_fleet(storage, args.local_workers)
            if args.local_workers else []
        )
        try:
            run = driver.submit(spec, tenant=args.tenant)
            print(f"[INFO] Eval run {run.id}: {run.num_points} points, "
                  f"{len(run.shards)} shard jobs queued.")
            if args.no_wait:
                return 0
            run = driver.wait(run.id, timeout_s=args.timeout)
        finally:
            for m in members:
                m.stop()
        status = driver.status(run.id)
        _print_eval_run(status["run"], status["points"])
        return 0 if run.status == "completed" else 1

    from predictionio_tpu.evalfleet.records import EvalRecordStore

    store = EvalRecordStore(_storage())
    if action == "list":
        runs = store.list_runs(
            engine_id=args.engine, status=args.status, tenant=args.tenant
        )
        print(f"{'RUN':24s} {'ENGINE':12s} {'STATUS':10s} {'POINTS':>6s} "
              f"{'METRIC':14s} {'WINNER':>8s}")
        for r in runs:
            winner = "-" if r.winner_score is None else f"{r.winner_score:.4g}"
            print(f"{r.id:24s} {r.engine_id:12s} {r.status:10s} "
                  f"{r.num_points:>6d} {r.metric_header:14s} {winner:>8s}")
        return 0
    if action in ("show", "status"):
        from predictionio_tpu.evalfleet.driver import EvalDriver

        driver = EvalDriver(_storage())
        try:
            status = driver.status(args.run_id)
        except KeyError as e:
            return _fail(str(e))
        _print_eval_run(status["run"], status["points"])
        if action == "status":
            print(f"progress   {status['points_done']}/"
                  f"{status['points_total']} points")
            for s in status["shards"]:
                fold = "all" if s["fold"] is None else s["fold"]
                print(f"  shard {s['job_id']}  group {s['group']} "
                      f"fold {fold}  {s['status']}"
                      + (f"  worker {s['worker_id']}"
                         if s.get("worker_id") else ""))
        return 0
    if action == "gc":
        from predictionio_tpu.utils.env import env_int

        removed = store.gc(keep=args.keep if args.keep is not None
                           else env_int("PIO_EVAL_RETENTION"))
        removed += store.compact(min_age_s=0.0 if args.now else 60.0)
        print(f"[INFO] Eval GC: {removed} events removed.")
        return 0
    return _fail(f"unknown eval action {action!r}")


def cmd_tune(args) -> int:
    from predictionio_tpu.evalfleet.specs import EvalSpec
    from predictionio_tpu.evalfleet.tuning import tune

    storage = _storage()
    try:
        spec = EvalSpec.load(args.spec)
    except (OSError, ValueError, KeyError) as e:
        return _fail(f"bad eval spec: {e}")
    members = (
        _local_fleet(storage, args.local_workers)
        if args.local_workers else []
    )
    try:
        run, preset = tune(
            storage, spec, tenant=args.tenant, timeout_s=args.timeout
        )
    finally:
        for m in members:
            m.stop()
    if preset is None:
        return _fail(f"tune: run {run.id} ended {run.status} without a "
                     f"winner")
    scope = f"tenant {preset.tenant}" if preset.tenant else "global"
    print(f"[INFO] Eval run {run.id} completed: winner point "
          f"{run.winner_index} ({run.metric_header}={run.winner_score}).")
    print(f"[INFO] Winner parked as {scope} retrain preset for engine "
          f"{preset.engine_id} — the next periodic retrain trains it.")
    return 0


def cmd_eventserver(args) -> int:
    from predictionio_tpu.data.api.server import EventServer, EventServerConfig

    return _serve_until_interrupt(
        EventServer(
            _storage(),
            EventServerConfig(
                ip=args.ip, port=args.port, stats=args.stats,
                log_url=args.log_url,
            ),
        ),
        f"[INFO] Event Server is listening at http://{args.ip}:{{port}}.",
    )


# ---------------------------------------------------------------------------
# status / export / import (reference Console.status, EventsToFile, FileToEvents)
# ---------------------------------------------------------------------------


def cmd_template(args) -> int:
    from predictionio_tpu.tools.template import list_templates, scaffold

    if args.template_action == "list":
        for t in list_templates():
            print(f"{t.name:16s} {t.description}")
        return 0
    # get
    try:
        dest = scaffold(args.name, args.directory, args.package)
    except (ValueError, FileExistsError) as e:
        return _fail(str(e))
    print(f"[INFO] Engine template '{args.name}' scaffolded at {dest}.")
    print("[INFO] Next: edit engine.json, then `pio train` from that "
          "directory.")
    return 0


def cmd_storage_server(args) -> int:
    from predictionio_tpu.data.api.storage_server import StorageServer

    server = StorageServer(
        _storage(), host=args.ip, port=args.port, auth_key=args.auth_key
    )
    print(
        f"[INFO] Storage server is listening at http://{args.ip}:{server.port}."
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.shutdown()
    return 0


def cmd_adminserver(args) -> int:
    from predictionio_tpu.tools.admin import AdminServer

    return _serve_until_interrupt(
        AdminServer(_storage(), ip=args.ip, port=args.port),
        f"[INFO] Admin server is listening at http://{args.ip}:{{port}}.",
    )


def cmd_dashboard(args) -> int:
    from predictionio_tpu.tools.dashboard import Dashboard

    return _serve_until_interrupt(
        Dashboard(
            _storage(), ip=args.ip, port=args.port,
            monitor_targets=getattr(args, "monitor_targets", None),
        ),
        f"[INFO] Dashboard is listening at http://{args.ip}:{{port}}.",
    )


def cmd_status(args) -> int:
    storage = _storage()
    print("[INFO] Inspecting predictionio_tpu...")
    import predictionio_tpu

    print(f"[INFO] predictionio_tpu {predictionio_tpu.__version__}")
    import jax

    print(f"[INFO] jax {jax.__version__}; devices: {jax.devices()}")
    print("[INFO] Verifying storage backend connections...")
    try:
        for line in storage.verify_all_data_objects():
            print(f"[INFO]   {line}")
    except Exception as e:
        return _fail(f"storage verification failed: {e}")
    events = storage.get_events()
    if hasattr(events, "segment_stats"):
        # segmentfs (ISSUE 13): surface the columnar store's shape —
        # sealed segment count, unsealed tail depth, dead rows awaiting
        # compaction — per app the metadata store knows about
        try:
            for app in storage.get_meta_data_apps().get_all():
                st = events.segment_stats(app.id)
                print(
                    f"[INFO]   segmentfs app {app.id} ({app.name}): "
                    f"{st['segments']} segment(s), "
                    f"{st['sealed_rows']} sealed + {st['tail_rows']} tail "
                    f"row(s), {st['dead_rows']} dead, "
                    f"rev {st['max_revision']}"
                )
        except Exception as e:
            print(f"[WARN] segmentfs stats unavailable: {e}")
    if getattr(args, "event_url", None):
        # live-server passthrough (ISSUE 14 satellite): the RUNNING
        # event server's segment surface — the daemon shape where this
        # process has no direct segmentfs handle
        try:
            import urllib.parse

            key = getattr(args, "access_key", None) or ""
            url = (
                args.event_url.rstrip("/")
                + "/segments/stats?accessKey="
                + urllib.parse.quote(key)
            )
            import json as _json
            import urllib.request

            with urllib.request.urlopen(url, timeout=5) as resp:
                st = _json.loads(resp.read().decode())
            print(
                f"[INFO] event server {args.event_url}: "
                f"{st.get('segments')} segment(s), "
                f"{st.get('sealed_rows')} sealed + "
                f"{st.get('tail_rows')} tail row(s), "
                f"{st.get('dead_rows')} dead, "
                f"rev {st.get('max_revision')}"
            )
        except Exception as e:
            print(f"[WARN] event-server segment stats unavailable: {e}")
    try:
        manifests = storage.get_meta_data_engine_manifests().get_all()
    except Exception as e:
        manifests = []
        print(f"[WARN] could not list engine manifests: {e}")
    if manifests:
        print("[INFO] Registered engines (trained at least once):")
        for m in manifests:
            print(
                f"[INFO]   {m.id} v{m.version}: {m.engine_factory}"
                + (f" — {m.description}" if m.description else "")
            )
    _print_registry_summary()
    print("[INFO] (sleeping 0 seconds) Your system is all ready to go.")
    return 0


def _print_registry_summary() -> None:
    """Render the process-default registry (train-stage timings etc.) —
    the same data a server scrape would show, in console form."""
    from predictionio_tpu.obs import get_default_registry

    snap = get_default_registry().snapshot()
    interesting = {
        k: v for k, v in snap.items() if not k.startswith("jax_")
    }
    if not interesting:
        return
    print("[INFO] Process metrics (registry snapshot):")
    for name, fam in sorted(interesting.items()):
        for row in fam["values"]:
            labels = ",".join(f"{k}={v}" for k, v in row["labels"].items())
            where = f"{name}{{{labels}}}" if labels else name
            if fam["type"] == "histogram":
                print(
                    f"[INFO]   {where}: count={row['count']} "
                    f"mean={row['mean'] * 1e3:.1f}ms "
                    f"p50={row['p50'] * 1e3:.1f}ms "
                    f"p99={row['p99'] * 1e3:.1f}ms"
                )
            else:
                print(f"[INFO]   {where}: {row['value']:g}")


def cmd_metrics(args) -> int:
    if args.url:
        import urllib.request

        with urllib.request.urlopen(args.url, timeout=10) as r:
            print(r.read().decode(), end="")
        return 0
    if args.summary:
        _print_registry_summary()
        return 0
    from predictionio_tpu.obs import get_default_registry

    print(get_default_registry().render(), end="")
    return 0


KNOBS_BEGIN = "<!-- knobs:begin -->"
KNOBS_END = "<!-- knobs:end -->"


def _readme_knob_drift(readme_path: str, table: str) -> Optional[str]:
    """None when the README knob section matches the registry; else a
    human-readable drift description."""
    try:
        with open(readme_path, encoding="utf-8") as f:
            text = f.read()
    except OSError as e:
        return f"cannot read {readme_path}: {e}"
    try:
        start = text.index(KNOBS_BEGIN) + len(KNOBS_BEGIN)
        end = text.index(KNOBS_END)
    except ValueError:
        return (
            f"{readme_path} has no {KNOBS_BEGIN} ... {KNOBS_END} "
            "markers around the Configuration knobs table"
        )
    current = text[start:end].strip()
    if current != table.strip():
        return (
            f"{readme_path} knob table is stale — regenerate with "
            "`pio lint --knobs` and paste between the markers"
        )
    return None


def cmd_lint(args) -> int:
    """`pio lint`: run the in-tree invariant analyzer (ISSUE 12)."""
    import json as _json

    from predictionio_tpu.analysis import lint as _lint
    from predictionio_tpu.utils.env import knobs_markdown

    if args.tsan_report is not None:
        path = args.tsan_report or "tsan-report.json"
        try:
            with open(path, encoding="utf-8") as f:
                rep = _json.load(f)
        except OSError as e:
            return _fail(f"cannot read tsan report: {e}")
        print(_json.dumps(rep, indent=2, sort_keys=True))
        n = int(rep.get("findings_count", 0))
        print(f"tsan findings: {n}")
        return 1 if n else 0

    if args.knobs:
        table = knobs_markdown()
        if args.check_readme:
            drift = _readme_knob_drift(args.check_readme, table)
            if drift is not None:
                print(drift, file=sys.stderr)
                return 1
            print(f"{args.check_readme} knob table is fresh")
            return 0
        print(table, end="")
        return 0

    rules = _lint.all_rules()
    if args.rule:
        known = {r.name for r in rules}
        unknown = [r for r in args.rule if r not in known]
        if unknown:
            return _fail(
                f"unknown rule(s) {unknown}; available: {sorted(known)}"
            )
        rules = [r for r in rules if r.name in args.rule]
    paths = args.paths or [_lint.package_root()]
    findings, errors = _lint.lint_paths(paths, rules)
    if args.json:
        print(_json.dumps(
            {
                "findings": [f.as_dict() for f in findings],
                "errors": errors,
            },
            indent=2,
        ))
    else:
        for f in findings:
            print(f)
        for e in errors:
            print(f"error: {e}", file=sys.stderr)
        print(
            f"pio lint: {len(findings)} finding(s), {len(errors)} "
            f"error(s) across {len(rules)} rule(s)"
        )
    return 1 if findings or errors else 0


def _fetch_json(url: str, path: str, timeout: float = 10.0) -> dict:
    """GET a server JSON surface: the one fetch helper every remote
    (`--url`) subcommand shares."""
    import json as _json
    import urllib.request

    with urllib.request.urlopen(url.rstrip("/") + path, timeout=timeout) as r:
        return _json.loads(r.read().decode())


def _fetch_debug_traces(url: str, params: str = "") -> dict:
    return _fetch_json(
        url, "/debug/traces" + (f"?{params}" if params else "")
    )


def _print_span_tree(spans: list[dict]) -> None:
    """Indent spans by parent links; remote/missing parents root the
    subtree (a storage daemon's fragment viewed on its own)."""
    ids = {s["span_id"] for s in spans}
    children: dict = {}
    roots = []
    for s in sorted(spans, key=lambda s: s["start"]):
        parent = s.get("parent_span_id")
        if parent in ids:
            children.setdefault(parent, []).append(s)
        else:
            roots.append(s)

    def walk(s: dict, depth: int) -> None:
        attrs = s.get("attrs", {})
        extra = " ".join(
            f"{k}={v}" for k, v in attrs.items() if k != "server"
        )
        flag = " ERROR" if s.get("error") else ""
        server = attrs.get("server")
        where = f" [{server}]" if server else ""
        print(
            f"[INFO] {'  ' * depth}{s['name']}{where} "
            f"{s['duration_ms']:.3f} ms{flag}"
            + (f"  ({extra})" if extra else "")
        )
        for c in children.get(s["span_id"], ()):
            walk(c, depth + 1)

    for r in roots:
        walk(r, 0)


def _fleet_collector():
    """This process's fleet trace collector, or a CLI failure when no
    gateway/monitor in this process is running one."""
    from predictionio_tpu.obs.monitor import get_monitor

    col = get_monitor().collector
    if col is None:
        _fail(
            "no fleet trace collector in this process — pass --url "
            "pointing at a gateway started with PIO_TRACE_COLLECT=1"
        )
    return col


def cmd_trace(args) -> int:
    """`pio trace list|show|export|stats` — the retained (tail-sampled)
    traces of a running server (--url http://host:port) or of this
    process; `stats` is where the last --window seconds went by span
    name, over every span (before sampling).
    With --fleet, the ASSEMBLED cross-process traces of the fleet
    collector (gateway root + per-attempt children + replica-side
    server spans stitched by request id) instead of one process's
    local fragments."""
    import json as _json

    from predictionio_tpu.obs.spans import get_default_recorder

    url = getattr(args, "url", None)
    fleet = getattr(args, "fleet", False)
    action = args.trace_action
    if action == "stats":
        if url:
            table = _fetch_debug_traces(
                url, f"stats=1&window={args.window}"
            )["spans"]
        else:
            table = get_default_recorder().stats(
                time.monotonic() - args.window
            )
        print(f"[INFO] spans ended in the last {args.window:g} s "
              f"({len(table)} name(s)), by self time:")
        print(f"[INFO]   {'span':<28} {'count':>8} {'total_s':>10} "
              f"{'self_s':>10} {'mean_ms':>9}")
        for name, row in sorted(
            table.items(), key=lambda kv: -kv[1]["self_s"]
        ):
            print(
                f"[INFO]   {name:<28} {row['count']:>8} "
                f"{row['total_s']:>10.3f} {row['self_s']:>10.3f} "
                f"{1e3 * row['total_s'] / row['count']:>9.2f}"
            )
        return 0
    if action == "list":
        if url:
            params = f"limit={args.limit}"
            if fleet:
                params = "fleet=1&" + params
            data = _fetch_debug_traces(url, params)
            summaries = data["traces"]
            cfg = data.get("collector" if fleet else "sampling", {})
        elif fleet:
            col = _fleet_collector()
            if col is None:
                return 1
            summaries, cfg = col.summaries(limit=args.limit), col.status()
        else:
            rec = get_default_recorder()
            summaries, cfg = rec.summaries(limit=args.limit), rec.config()
        kind = "assembled fleet" if fleet else "retained"
        print(
            f"[INFO] {len(summaries)} {kind} trace(s) "
            f"({'collector' if fleet else 'sampling'}: {cfg})"
        )
        for s in summaries:
            # fleet rows carry every server the trace crossed; local
            # rows only ever saw one
            servers = s.get("servers") or (
                [s["server"]] if s.get("server") else []
            )
            where = f" {','.join(servers)}" if servers else ""
            path = f" {s['path']}" if s.get("path") else ""
            err = " ERROR" if s["error"] else ""
            print(
                f"[INFO]   {s['trace_id']}  {s['root']}{where}{path}  "
                f"{s['duration_ms']:.1f} ms  {s['spans']} spans  "
                f"kept={s['kept']}{err}"
            )
        return 0
    if action == "show":
        if url:
            params = f"trace_id={args.trace_id}"
            if fleet:
                params = "fleet=1&" + params
            data = _fetch_debug_traces(url, params)
            spans = data["spans"]
        elif fleet:
            col = _fleet_collector()
            if col is None:
                return 1
            spans = col.get_trace(args.trace_id)
        else:
            spans = [
                s.to_dict()
                for s in get_default_recorder().get_trace(args.trace_id)
            ]
        if not spans:
            return _fail(f"no retained trace {args.trace_id!r}")
        print(f"[INFO] Trace {args.trace_id} ({len(spans)} spans):")
        _print_span_tree(spans)
        return 0
    # export: Chrome trace-event JSON → open at https://ui.perfetto.dev
    if url:
        params = "format=perfetto"
        if args.trace_id:
            params = f"trace_id={args.trace_id}&" + params
        if fleet:
            params = "fleet=1&" + params
        export = _fetch_debug_traces(url, params)
    elif fleet:
        col = _fleet_collector()
        if col is None:
            return 1
        export = col.perfetto_export(args.trace_id)
    else:
        export = get_default_recorder().perfetto_export(args.trace_id)
    if not export.get("traceEvents"):
        return _fail(
            f"no retained trace {args.trace_id!r}" if args.trace_id
            else "no retained traces to export"
        )
    with open(args.output, "w") as f:
        _json.dump(export, f)
    print(
        f"[INFO] Wrote {len(export['traceEvents'])} trace events to "
        f"{args.output} — load it at https://ui.perfetto.dev"
    )
    return 0


def _fetch_profile(url: str) -> dict:
    return _fetch_json(url, "/debug/profile")


def cmd_profile(args) -> int:
    """`pio profile list|show|capture` — device-profile accounting of a
    running server (--url http://host:port) or of this process."""
    action = args.profile_action
    url = getattr(args, "url", None)
    if action == "capture":
        # on-demand jax.profiler window: remote via the guarded admin
        # endpoint, or in-process when --dir names a writable directory
        if url:
            import json as _json
            import urllib.error
            import urllib.request

            req = urllib.request.Request(
                url.rstrip("/") + "/debug/profile/capture",
                data=_json.dumps({"seconds": args.seconds}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            try:
                with urllib.request.urlopen(req, timeout=args.seconds + 30) as r:
                    result = _json.loads(r.read().decode())
            except urllib.error.HTTPError as e:
                detail = e.read().decode(errors="replace")
                return _fail(f"capture refused ({e.code}): {detail}")
            print(f"[INFO] XLA profile captured to {result['dir']} "
                  f"(on the server host; inspect with tensorboard/xprof)")
            return 0
        if not args.dir:
            return _fail("profile capture needs --url or --dir")
        # the local capture is jax-bound by definition — pay the import
        # here so capture_trace (which never imports jax itself) can run
        import jax  # noqa: F401

        from predictionio_tpu.obs import devprof

        result = devprof.capture_trace(args.dir, args.seconds)
        print(f"[INFO] XLA profile captured to {result['dir']} "
              f"(inspect with tensorboard --logdir)")
        return 0

    if url:
        rep = _fetch_profile(url)
    else:
        from predictionio_tpu.obs import devprof

        rep = devprof.report()
    plat = rep.get("platform", {})
    if action == "list":
        peak = plat.get("peak_flops")
        peak_s = f"{peak / 1e12:g} TFLOP/s" if peak else "unknown"
        print(
            f"[INFO] platform={plat.get('platform')} "
            f"kind={plat.get('device_kind')} peak={peak_s} "
            f"(source: {plat.get('peak_source')})"
        )
        rows = rep.get("executables", [])
        if not rows:
            print("[INFO] no profiled executables yet")
            return 0
        print(
            f"[INFO] {'executable':<28} {'calls':>7} {'dev_sec':>9} "
            f"{'compile_s':>9} {'GFLOP':>10} {'dtype':>6} {'mfu':>9} "
            f"{'hbm%':>7}"
        )
        for r in rows:
            u = r.get("mfu")
            h = r.get("hbm_fraction_of_roof")
            print(
                f"[INFO] {r['name']:<28} {r['invocations']:>7} "
                f"{r['device_seconds']:>9.3f} {r['compile_seconds']:>9.2f} "
                f"{r['flops_total'] / 1e9:>10.2f} "
                f"{r.get('dtype', 'bf16'):>6} "
                f"{(f'{u:.5f}' if u is not None else '-'):>9} "
                f"{(f'{100 * h:.1f}' if h is not None else '-'):>7}"
            )
        pad = rep.get("padding", {})
        if pad.get("batches"):
            print(
                f"[INFO] padding: {pad['batches']} batches, mean ratio "
                f"{pad['mean_padding_ratio']:.3f}, wasted "
                f"{pad['wasted_flops'] / 1e9:.2f} GFLOP"
            )
        return 0
    # show
    row = next(
        (r for r in rep.get("executables", []) if r["name"] == args.name),
        None,
    )
    if row is None:
        return _fail(f"no profiled executable {args.name!r}")
    print(f"[INFO] {row['name']}:")
    for k, v in row.items():
        if k == "name":
            continue
        print(f"[INFO]   {k}: {v}")
    return 0


def cmd_faults(args) -> int:
    """`pio faults list|set|clear` — fault-injection registry of this
    process, or of a running server via --url (its guarded
    POST /debug/faults; the server needs PIO_FAULTS_ADMIN=1)."""
    import json as _json
    import urllib.error
    import urllib.request

    from predictionio_tpu.resilience import faults

    url = getattr(args, "url", None)
    action = args.faults_action

    def _remote(method: str, body: Optional[dict] = None) -> dict:
        req = urllib.request.Request(
            url.rstrip("/") + "/debug/faults",
            data=_json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"},
            method=method,
        )
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return _json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            detail = e.read().decode(errors="replace")
            raise CommandError(f"fault admin refused ({e.code}): {detail}")

    def _print(specs: list) -> None:
        if not specs:
            print("[INFO] no active fault specs (registry inert)")
            return
        print(f"[INFO] {len(specs)} active fault spec(s):")
        for s in specs:
            extra = (
                f" param={s['param']}" if s["mode"] == "delay" else ""
            ) + (f" seed={s['seed']}" if s.get("seed") is not None else "")
            # print the full registry key (point@scope): it round-trips
            # into `pio faults clear <key>` — printing the bare point
            # for a scoped spec would name a key that clears nothing
            name = s["point"] + (
                f"@{s['scope']}" if s.get("scope") else ""
            )
            print(
                f"[INFO]   {name}: {s['mode']} "
                f"p={s['probability']}{extra}"
            )

    if action == "list":
        specs = _remote("GET")["faults"] if url else faults.specs()
        _print(specs)
        return 0
    if action == "set":
        if url:
            body: dict = {"set": args.spec}
            if args.seed is not None:
                body["seed"] = args.seed
            _print(_remote("POST", body)["faults"])
            return 0
        try:
            for spec in faults.parse_specs(args.spec, args.seed):
                faults.install(spec)
        except faults.FaultSpecError as e:
            return _fail(str(e))
        _print(faults.specs())
        return 0
    # clear
    point = getattr(args, "point", None)
    if url:
        _print(_remote("POST", {"clear": point if point else True})["faults"])
        return 0
    faults.clear(point)
    _print(faults.specs())
    return 0


# ---------------------------------------------------------------------------
# monitoring plane (ISSUE 8): monitor / alerts / tsdb
# ---------------------------------------------------------------------------


def cmd_monitor(args) -> int:
    """`pio monitor` — a standalone fleet-aggregation process: scrape
    the configured targets' /metrics into the in-process TSDB, run the
    SLO engine over it, and print the fleet + alert state each pass."""
    import os
    import time as _time

    from predictionio_tpu.obs.monitor import (
        FleetScraper,
        SLOEngine,
        TraceCollector,
        get_monitor,
        load_slos,
        parse_targets,
    )
    from predictionio_tpu.utils.env import env_bool

    targets = parse_targets(
        args.targets or _env_str("PIO_MONITOR_TARGETS")
    )
    if not targets:
        return _fail(
            "no scrape targets: pass --targets name=url[,name=url] or "
            "set PIO_MONITOR_TARGETS"
        )
    monitor = get_monitor()
    scraper = FleetScraper(
        monitor.tsdb, targets, interval_s=args.interval
    )
    # the trace collector rides the same targets: the monitor process
    # assembles the fleet's cross-process traces too (PIO_TRACE_COLLECT)
    collector = None
    if env_bool("PIO_TRACE_COLLECT"):
        collector = TraceCollector(
            targets=list(targets), interval_s=args.interval
        )
        monitor.set_collector(collector)
    exprs = list(getattr(args, "expr", None) or [])
    if exprs:
        # parse eagerly so a typo fails before the first scrape pass
        from predictionio_tpu.obs.monitor.expr import ExprError, parse

        for e in exprs:
            try:
                parse(e)
            except ExprError as exc:
                return _fail(f"bad --expr {e!r}: {exc}")
    specs = load_slos(args.slos) if args.slos else load_slos()
    engine = None
    if specs:
        engine = SLOEngine(
            monitor.tsdb, specs, interval_s=max(args.interval, 1.0)
        )
    deadline = (
        _time.monotonic() + args.duration if args.duration else None
    )
    try:
        while True:
            ups = scraper.scrape_once()
            if collector is not None:
                collector.collect_once()
            if engine is not None:
                engine.evaluate_once()
            stamp = _time.strftime("%H:%M:%S")
            fleet = " ".join(
                f"{inst}={'up' if ok else 'DOWN'}"
                for inst, ok in sorted(ups.items())
            )
            traces = (
                f"  traces={collector.status()['assembled']}"
                if collector is not None else ""
            )
            print(f"[INFO] {stamp} fleet: {fleet}{traces}")
            for e in exprs:
                # evaluated per pass over the freshly-scraped TSDB
                from predictionio_tpu.obs.monitor.expr import (
                    ExprError,
                    evaluate_rows,
                )

                try:
                    rows = evaluate_rows(monitor.tsdb, e)
                except ExprError as exc:
                    print(f"[WARN]   expr {e}: {exc}")
                    continue
                if not rows:
                    print(f"[INFO]   expr {e} = (no data)")
                    continue
                for row in rows:
                    lbls = ",".join(
                        f"{k}={v}"
                        for k, v in sorted(row["labels"].items())
                    )
                    where = f"{{{lbls}}}" if lbls else ""
                    print(
                        f"[INFO]   expr {e}{where} = {row['value']:g}"
                    )
            if engine is not None:
                for row in engine.payload()["slos"]:
                    fast = row["fast_burn"]
                    print(
                        f"[INFO]   slo {row['slo']}: {row['state']} "
                        f"(fast burn "
                        f"{'-' if fast is None else f'{fast:.2f}'} / "
                        f"threshold {row['burn_threshold']})"
                    )
            if deadline is not None and _time.monotonic() >= deadline:
                return 0
            _time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def cmd_alerts(args) -> int:
    """`pio alerts list|show` — SLO alert states of this process, or a
    running server via --url (its GET /alerts)."""
    from predictionio_tpu.obs.monitor import get_monitor

    url = getattr(args, "url", None)
    payload = (
        _fetch_json(url, "/alerts") if url
        else get_monitor().alerts_payload()
    )
    rows = payload.get("slos", [])
    if args.alerts_action == "list":
        if not rows:
            print(
                "[INFO] no SLOs configured "
                f"({payload.get('message', 'set PIO_SLOS')})"
            )
            return 0
        print(f"[INFO] {len(rows)} SLO(s), firing: "
              f"{payload.get('firing') or 'none'}")
        for r in rows:
            fast, slow = r.get("fast_burn"), r.get("slow_burn")
            print(
                f"[INFO]   {r['slo']}: {r['state']}  fast="
                f"{'-' if fast is None else f'{fast:.2f}'} slow="
                f"{'-' if slow is None else f'{slow:.2f}'} "
                f"threshold={r.get('burn_threshold')} "
                f"samples={r.get('fast_samples')}"
            )
        return 0
    row = next((r for r in rows if r["slo"] == args.name), None)
    if row is None:
        return _fail(f"no SLO {args.name!r}")
    print(f"[INFO] {row['slo']}:")
    for k, v in row.items():
        if k != "slo":
            print(f"[INFO]   {k}: {v}")
    return 0


def _server_call(
    base: str, path: str, body: Optional[dict] = None
) -> dict:
    """POST (with a JSON body) or GET `base+path` on a running query
    server, turning HTTP/transport failures into CommandError — shared
    by the rollout and online command families."""
    import json as _json
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        base.rstrip("/") + path,
        data=_json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"},
        method="POST" if body is not None else "GET",
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return _json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        detail = e.read().decode(errors="replace")
        try:
            detail = _json.loads(detail).get("message", detail)
        except ValueError:
            pass
        raise CommandError(f"query server refused ({e.code}): {detail}")
    except OSError as e:
        raise CommandError(f"query server unreachable at {base}: {e}")


def cmd_online(args) -> int:
    """`pio online status|pause|resume|cursors` — the streaming fold-in
    consumer on a running query server (--url), or the durable cursor
    records in storage (`cursors`)."""
    action = args.online_action
    if action == "cursors":
        from predictionio_tpu.deploy.registry import LifecycleRecordStore
        from predictionio_tpu.online import CURSOR_ENTITY

        records = LifecycleRecordStore(_storage()).fold(CURSOR_ENTITY)
        if not records:
            print("[INFO] no online consumer cursors recorded")
            return 0
        for cid, rec in sorted(records.items()):
            print(f"[INFO] {cid}:")
            print(f"[INFO]   cursor: {rec.get('cursor')}")
            for k in (
                "events_consumed", "events_folded", "users_folded",
                "items_folded", "ticks",
            ):
                print(f"[INFO]   {k}: {rec.get(k, 0)}")
        return 0

    if action == "status":
        st = _server_call(args.url, "/online/status")
    elif action == "pause":
        st = _server_call(
            args.url, "/online/pause",
            {"reason": args.reason or "operator pause"},
        )
    else:  # resume
        st = _server_call(args.url, "/online/resume", {})
    print(f"[INFO] online consumer: {st.get('state')}")
    if st.get("state") != "attached":
        return 0
    paused = st.get("paused")
    print(f"[INFO]   paused: {paused or 'no'}")
    print(f"[INFO]   cursor {st.get('cursor_id')}: {st.get('cursor')}")
    print(f"[INFO]   drift: {st.get('drift')} "
          f"(threshold {st.get('drift_threshold')})")
    for k, v in (st.get("counters") or {}).items():
        print(f"[INFO]   {k}: {v}")
    return 0


def cmd_tsdb(args) -> int:
    """`pio tsdb query` — the in-process time-series history of this
    process, or a running server via --url (its GET /debug/tsdb)."""
    from predictionio_tpu.obs.monitor import get_monitor

    url = getattr(args, "url", None)
    qs: dict = {}
    if getattr(args, "expr", None):
        qs["expr"] = args.expr
    if args.name:
        qs["name"] = args.name
    if args.labels:
        qs["labels"] = args.labels
    if args.window is not None:
        qs["window_s"] = str(args.window)
    if args.agg:
        qs["agg"] = args.agg
        if args.q is not None:
            qs["q"] = str(args.q)
    if url:
        from urllib.parse import urlencode

        payload = _fetch_json(
            url, "/debug/tsdb" + (f"?{urlencode(qs)}" if qs else "")
        )
    else:
        payload = get_monitor().tsdb_payload(qs)
    if not payload.get("enabled", True):
        print("[INFO] monitoring disabled (PIO_TSDB=0)")
        return 0
    if "expr" in payload:
        # series-algebra evaluation (ISSUE 17)
        if "error" in payload:
            return _fail(f"expression error: {payload['error']}")
        rows = payload.get("result") or []
        print(f"[INFO] {payload['expr']}")
        if not rows:
            print("[INFO]   (no data)")
            return 0
        for row in rows:
            labels = ",".join(
                f"{k}={v}" for k, v in sorted(row["labels"].items())
            )
            print(f"[INFO]   {{{labels}}} = {row['value']:g}")
        return 0
    if "value" in payload:
        print(
            f"[INFO] {payload['agg']}({payload['name']}"
            + (f", window={payload.get('window_s')}s" if payload.get(
                "window_s") else "")
            + f") = {payload['value']}"
        )
        return 0
    series = payload.get("series", [])
    if not args.name:
        print(
            f"[INFO] {payload.get('series_count', len(series))} series "
            f"(capacity {payload.get('capacity')} pts, "
            f"{payload.get('dropped_series', 0)} dropped at the "
            "cardinality cap)"
        )
        durable = payload.get("durable")
        if durable:
            # durable tier summary (ISSUE 18)
            wal = durable.get("wal", {})
            print(
                f"[INFO] durable tier at {durable.get('dir')}: "
                f"{wal.get('segments', 0)} wal segment(s), "
                f"{wal.get('pending', 0)} pending pts, replayed "
                f"{durable.get('replayed_points', 0)} pts at attach"
            )
            for tier, st in (durable.get("tiers") or {}).items():
                span = (
                    f"{st['max_t'] - st['min_t']:.0f}s span"
                    if st.get("min_t") is not None else "empty"
                )
                print(
                    f"[INFO]   tier {tier}: {st.get('blocks', 0)} "
                    f"block(s), {st.get('series', 0)} series, "
                    f"{st.get('bytes', 0)} bytes, {span}"
                )
        for s in series:
            labels = ",".join(f"{k}={v}" for k, v in s["labels"].items())
            where = f"{s['name']}{{{labels}}}" if labels else s["name"]
            print(
                f"[INFO]   {where} [{s['kind']}] {s['points']} pts "
                f"last={s['last']}"
            )
        return 0
    for s in series:
        labels = ",".join(f"{k}={v}" for k, v in s["labels"].items())
        where = f"{s['name']}{{{labels}}}" if labels else s["name"]
        print(f"[INFO] {where} [{s['kind']}] {len(s['points'])} pts:")
        for t, v in s["points"][-(args.last or len(s["points"])):]:
            print(f"[INFO]   {t:.3f}  {v:g}")
    return 0


# ---------------------------------------------------------------------------
# model lifecycle (ISSUE 5): jobs / models / rollout
# ---------------------------------------------------------------------------


def cmd_jobs(args) -> int:
    """`pio jobs submit|list|show|logs|worker` — the background training
    queue. Storage-backed: submit from any host sharing the stores; a
    `worker` (here or embedded elsewhere) picks jobs up."""
    from predictionio_tpu.deploy.scheduler import (
        JobQueue,
        SchedulerConfig,
        TrainScheduler,
    )

    storage = _storage()
    queue = JobQueue(storage)
    action = args.jobs_action
    if action == "submit":
        from predictionio_tpu.workflow.core import load_variant

        try:
            variant = load_variant(args.variant)
            job = queue.submit(
                variant,
                timeout_s=args.timeout,
                period_s=args.period,
                max_attempts=args.max_attempts,
            )
        except (OSError, ValueError) as e:
            return _fail(str(e))
        print(f"[INFO] submitted train job {job.id} "
              f"(engine {job.engine_id})")
        if job.period_s:
            print(f"[INFO] periodic retrain every {job.period_s:.0f}s")
        return 0
    if action == "list":
        jobs = queue.list(status=getattr(args, "status", None))
        if not jobs:
            print("[INFO] no train jobs")
            return 0
        print(f"[INFO] {len(jobs)} train job(s):")
        for j in jobs:
            extra = f" attempt={j.attempt}/{j.max_attempts}"
            if j.model_version:
                extra += f" version={j.model_version}"
            if j.last_error:
                extra += f" error={j.last_error!r}"
            print(f"[INFO]   {j.id} [{j.status}] engine={j.engine_id}"
                  f" created={j.created_at}{extra}")
        return 0
    if action == "gc":
        purged = queue.gc(keep=args.keep)
        print(f"[INFO] purged {len(purged)} terminal job record(s)"
              + (f": {', '.join(purged)}" if purged else ""))
        return 0
    if action in ("show", "logs"):
        job = queue.get(args.job_id)
        if job is None:
            return _fail(f"no job {args.job_id!r}")
        if action == "show":
            import json as _json

            print(_json.dumps(job.to_dict(), indent=2))
            return 0
        if not job.log_path:
            return _fail(f"job {job.id} has no log yet")
        try:
            with open(job.log_path, errors="replace") as f:
                sys.stdout.write(f.read())
        except OSError as e:
            return _fail(f"job log unreadable: {e}")
        return 0
    # worker
    cfg = SchedulerConfig()
    if args.log_dir:
        cfg.log_dir = args.log_dir
    scheduler = TrainScheduler(storage, cfg)
    if args.once:
        n = scheduler.run_pending_once()
        print(f"[INFO] ran {n} pending job(s)")
        return 0
    scheduler.start()
    print(f"[INFO] train scheduler running as {scheduler.worker_id} "
          "(Ctrl-C to stop)")
    try:
        while True:
            import time as _time

            _time.sleep(3600)
    except KeyboardInterrupt:
        print("[INFO] stopping scheduler (in-flight train finishes)")
        scheduler.stop()
        return 0


def cmd_fleet(args) -> int:
    """`pio fleet status|worker` — the multi-worker training fleet
    (ISSUE 10). `status` lists live/stale workers and the shared queue;
    `worker` runs a FleetMember: a CAS-claiming TrainScheduler with a
    heartbeating worker record, optionally joined to a multi-host
    jax.distributed collective via --coordinator/--num-processes."""
    from predictionio_tpu.fleet import (
        DistributedConfig,
        FleetConfig,
        FleetMember,
        fleet_status,
    )

    storage = _storage()
    if args.fleet_action == "status":
        import json as _json

        print(_json.dumps(fleet_status(storage), indent=2))
        return 0
    # worker
    from predictionio_tpu.deploy.scheduler import SchedulerConfig

    sched_cfg = SchedulerConfig()
    if args.log_dir:
        sched_cfg.log_dir = args.log_dir
    if args.max_concurrent:
        sched_cfg.max_concurrent = args.max_concurrent
    try:
        dist = DistributedConfig(
            coordinator_address=args.coordinator or None,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    except ValueError as e:
        return _fail(str(e))
    member = FleetMember(
        storage, scheduler_config=sched_cfg,
        fleet_config=FleetConfig(distributed=dist),
    )
    member.start()
    print(f"[INFO] fleet worker {member.worker_id} running"
          + (f" (process {dist.process_id}/{dist.num_processes} via "
             f"{dist.coordinator_address})" if dist.multi_host else "")
          + " (Ctrl-C to stop)")
    try:
        while True:
            import time as _time

            _time.sleep(3600)
    except KeyboardInterrupt:
        print("[INFO] stopping fleet worker (in-flight train finishes)")
        member.stop()
        return 0


def cmd_gateway(args) -> int:
    """`pio gateway run|status|replicas|drain` — the replicated serving
    tier's L7 router (ISSUE 15). `run` serves; `status` prints a running
    gateway's view (--url) ; `replicas` lists the shared registry's
    replica records; `drain` gracefully retires one replica."""
    import json as _json

    if args.gateway_action == "run":
        from predictionio_tpu.gateway import (
            Autoscaler,
            AutoscalerConfig,
            GatewayConfig,
            GatewayServer,
        )

        storage = _storage()
        cfg = GatewayConfig(ip=args.ip, port=args.port)
        if args.no_hedge:
            cfg.hedge = False
        autoscaler = None
        if args.autoscale:
            # policy without a manager: decisions are logged + counted
            # (gateway_scale_events_total) for an external actuator to
            # consume; the subprocess manager is a test tool
            autoscaler = Autoscaler(None, AutoscalerConfig(
                min_replicas=args.min_replicas,
                max_replicas=args.max_replicas,
            ))
        gw = GatewayServer(storage, cfg, autoscaler=autoscaler)
        port = gw.start()
        print(f"[INFO] gateway listening on {args.ip}:{port}")
        import threading as _threading

        try:
            _threading.Event().wait()
        except KeyboardInterrupt:
            pass
        finally:
            gw.stop()
        return 0
    if args.gateway_action == "replicas":
        from predictionio_tpu.gateway import ReplicaRegistry

        import time as _time

        rows = ReplicaRegistry(_storage()).list()
        if not rows:
            print("[INFO] no replica records")
            return 0
        now = _time.time()
        for r in sorted(rows, key=lambda r: r.id):
            age = max(0.0, now - r.heartbeat_at)
            print(
                f"[INFO] {r.id}: {r.url} engines={','.join(r.engines) or '-'} "
                f"dtype={r.serve_dtype} heartbeat_age={age:.1f}s"
                f"{' DRAINING' if r.draining else ''}"
            )
        return 0
    base = args.url or "http://127.0.0.1:8100"
    if args.gateway_action == "status":
        print(_json.dumps(
            _server_call(base, "/gateway/status"), indent=2
        ))
        return 0
    # drain
    result = _server_call(
        base, "/gateway/drain", {"replica": args.replica}
    )
    print(f"[INFO] drain initiated: {_json.dumps(result)}")
    return 0


def cmd_models(args) -> int:
    """`pio models list|show|promote|rollback|gc` — the version registry."""
    from predictionio_tpu.deploy.registry import ModelRegistry

    registry = ModelRegistry(_storage())
    action = args.models_action
    if action == "list":
        versions = registry.list(
            engine_id=getattr(args, "engine", None),
            status=getattr(args, "status", None),
        )
        if not versions:
            print("[INFO] no registered model versions")
            return 0
        print(f"[INFO] {len(versions)} model version(s):")
        for v in versions:
            note = f" ({v.reason})" if v.reason else ""
            print(f"[INFO]   {v.id} [{v.status}] "
                  f"{v.engine_id}/{v.engine_variant} "
                  f"instance={v.instance_id} params={v.params_hash}"
                  f" created={v.created_at}{note}")
        return 0
    if action == "gc":
        collected = registry.gc(
            keep=args.keep, delete_blobs=args.delete_blobs
        )
        print(f"[INFO] collected {len(collected)} version(s)"
              + (f": {', '.join(v.id for v in collected)}"
                 if collected else ""))
        return 0
    version = registry.get(args.version_id)
    if version is None:
        return _fail(f"no model version {args.version_id!r}")
    if action == "show":
        import json as _json

        print(_json.dumps(version.to_dict(), indent=2))
        lineage = registry.lineage(version.id)
        if len(lineage) > 1:
            print("[INFO] lineage: " + " <- ".join(v.id for v in lineage))
        return 0
    if action == "promote":
        v = registry.promote(version.id)
        print(f"[INFO] {v.id} is now live")
        return 0
    # rollback
    v = registry.rollback(version.id, args.reason or "operator rollback")
    print(f"[INFO] {v.id} marked rolled_back")
    return 0


def cmd_tenants(args) -> int:
    """`pio tenants list|show|new|set-quota|delete` — the multi-tenant
    serving control plane. Storage-backed: every query server's
    multiplexer picks edits up within its refresh interval."""
    import json as _json

    from predictionio_tpu.tenancy.tenants import Tenant, TenantStore

    store = TenantStore(_storage())
    action = args.tenants_action
    if action == "list":
        tenants = store.list()
        if not tenants:
            print("[INFO] no tenants")
            return 0
        print(f"[INFO] {len(tenants)} tenant(s):")
        for t in tenants:
            quota = ", ".join(
                f"{k}={v}"
                for k, v in (
                    ("qps", t.qps),
                    ("conc", t.max_concurrency),
                    ("dev_s/s", t.device_seconds_per_s),
                )
                if v is not None
            ) or "unlimited"
            print(f"[INFO]   {t.id} engine={t.engine_id}/"
                  f"{t.engine_variant} weight={t.weight} quota=[{quota}]"
                  + ("" if t.enabled else " DISABLED"))
        return 0
    if action == "new":
        try:
            tenant = store.upsert(Tenant(
                id=args.tenant_id,
                engine_id=args.engine,
                engine_version=args.engine_version,
                engine_variant=args.variant or args.engine,
                weight=args.weight,
                qps=args.qps,
                max_concurrency=args.max_concurrency,
                device_seconds_per_s=args.device_seconds,
                description=args.description or "",
            ))
        except ValueError as e:
            return _fail(str(e))
        print(f"[INFO] tenant {tenant.id} -> "
              f"{tenant.engine_id}/{tenant.engine_variant}")
        return 0
    if action == "delete":
        if not store.delete(args.tenant_id):
            return _fail(f"no tenant {args.tenant_id!r}")
        print(f"[INFO] tenant {args.tenant_id} deleted")
        return 0
    tenant = store.get(args.tenant_id)
    if tenant is None:
        return _fail(f"no tenant {args.tenant_id!r}")
    if action == "show":
        print(_json.dumps(tenant.to_dict(), indent=2))
        return 0
    # set-quota
    fields = {
        k: v
        for k, v in (
            ("weight", args.weight),
            ("qps", args.qps),
            ("max_concurrency", args.max_concurrency),
            ("device_seconds_per_s", args.device_seconds),
        )
        if v is not None
    }
    if not fields:
        return _fail("set-quota needs at least one of --weight/--qps/"
                     "--max-concurrency/--device-seconds")
    try:
        tenant = store.set_quota(args.tenant_id, **fields)
    except (KeyError, ValueError) as e:
        return _fail(str(e))
    print(f"[INFO] tenant {tenant.id} quota updated: weight={tenant.weight}"
          f" qps={tenant.qps} conc={tenant.max_concurrency}"
          f" dev_s/s={tenant.device_seconds_per_s}")
    return 0


def cmd_rollout(args) -> int:
    """`pio rollout start|status|abort` — drive a canary on a running
    query server (--url)."""
    action = args.rollout_action

    def _call(path: str, body: Optional[dict] = None) -> dict:
        return _server_call(args.url, path, body)

    def _print_status(st: dict) -> None:
        print(f"[INFO] rollout state: {st.get('state')}")
        if st.get("state") == "none":
            return
        v = st.get("version") or {}
        cfg = st.get("config") or {}
        print(f"[INFO]   version: {v.get('id')} "
              f"({v.get('engine_id')}/{v.get('engine_variant')})")
        print(f"[INFO]   traffic: {cfg.get('fraction', 0) * 100:.0f}%"
              + (" shadow" if cfg.get("shadow") else ""))
        if st.get("reason"):
            print(f"[INFO]   verdict: {st.get('last_action')} "
                  f"— {st['reason']}")
        for variant in ("live", "candidate"):
            s = st.get(variant) or {}
            agreement = (
                f" agreement={s['agreement']:.3f}"
                if "agreement" in s else ""
            )
            print(f"[INFO]   {variant}: n={s.get('count', 0)} "
                  f"err={s.get('error_rate', 0):.3f} "
                  f"p99={s.get('p99_ms', 0):.1f}ms{agreement}")

    try:
        if action == "start":
            body: dict = {}
            if args.version:
                body["version"] = args.version
            for k in ("fraction", "bake_s", "min_requests"):
                val = getattr(args, k, None)
                if val is not None:
                    body[k] = val
            if args.shadow:
                body["shadow"] = True
            _print_status(_call("/rollout/start", body))
        elif action == "abort":
            _print_status(
                _call("/rollout/abort", {"reason": args.reason or
                                         "operator abort"})
            )
        else:
            _print_status(_call("/rollout/status"))
    except CommandError as e:
        return _fail(str(e))
    return 0


def cmd_export(args) -> int:
    storage = _storage()
    app = _get_app(storage, args.app)
    if app is None:
        return 1
    channel_id = (
        common.resolve_channel(storage, app, args.channel)
        if args.channel
        else None
    )
    from predictionio_tpu.data.storage.base import EventQuery

    events_iter = storage.get_events().find(
        EventQuery(app_id=app.id, channel_id=channel_id)
    )
    n = 0
    if getattr(args, "format", "json") == "parquet":
        # reference parity: EventsToFile writes json OR parquet
        # (tools/.../export/EventsToFile.scala:42); batches stream
        # through one writer so a train-scale export stays O(batch)
        import pyarrow.parquet as pq

        from predictionio_tpu.data.storage.parquetfs import (
            _SCHEMA,
            events_to_table,
        )

        writer = pq.ParquetWriter(args.output, _SCHEMA)
        batch: list = []
        try:
            for e in events_iter:
                batch.append(e)
                n += 1
                if len(batch) >= 50_000:
                    writer.write_table(events_to_table(batch))
                    batch.clear()
            if batch:
                writer.write_table(events_to_table(batch))
        finally:
            writer.close()
    else:
        with open(args.output, "w") as f:
            for e in events_iter:
                f.write(e.to_json() + "\n")
                n += 1
    print(f"[INFO] Exported {n} events to {args.output}")
    return 0


def cmd_import(args) -> int:
    from predictionio_tpu.data.event import Event, EventValidation

    storage = _storage()
    app = _get_app(storage, args.app)
    if app is None:
        return 1
    channel_id = (
        common.resolve_channel(storage, app, args.channel)
        if args.channel
        else None
    )
    events = []
    errors = 0
    fmt = getattr(args, "format", None)
    if fmt == "parquet" or (fmt is None and args.input.endswith(".parquet")):
        # round-trips `pio export --format parquet` (beyond-reference:
        # FileToEvents reads json only). An explicit --format json
        # overrides the extension sniff.
        import pyarrow.parquet as pq

        from predictionio_tpu.data.storage.parquetfs import table_to_events

        def _bad_row(i, exc):
            nonlocal errors
            errors += 1
            print(f"[WARN] row {i}: {exc}", file=sys.stderr)

        try:
            table = pq.read_table(args.input)
        except Exception as exc:
            return _fail(
                f"{args.input} is not a readable parquet file: {exc}"
            )
        # with_index keeps ONE row numbering (physical, 0-based) across
        # decode and validation warnings, even after skipped rows
        for i, e in table_to_events(
            table, on_error=_bad_row, with_index=True
        ):
            try:
                EventValidation.validate(e)
                events.append(e)
            except Exception as exc:
                _bad_row(i, exc)
    else:
        with open(args.input) as f:
            for i, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    e = Event.from_json(line)
                    EventValidation.validate(e)
                    events.append(e)
                except Exception as exc:
                    errors += 1
                    print(f"[WARN] line {i}: {exc}", file=sys.stderr)
    storage.get_events().write(events, app.id, channel_id)
    print(f"[INFO] Imported {len(events)} events ({errors} malformed lines skipped)")
    return 0 if errors == 0 else 1


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio", description="predictionio_tpu operator console"
    )
    sub = p.add_subparsers(dest="command", required=True)

    # app
    app = sub.add_parser("app", help="manage apps").add_subparsers(
        dest="subcommand", required=True
    )
    s = app.add_parser("new")
    s.add_argument("name")
    s.add_argument("--description")
    s.add_argument("--access-key")
    s.set_defaults(func=cmd_app_new)
    s = app.add_parser("list")
    s.set_defaults(func=cmd_app_list)
    s = app.add_parser("show")
    s.add_argument("name")
    s.set_defaults(func=cmd_app_show)
    s = app.add_parser("delete")
    s.add_argument("name")
    s.add_argument("-f", "--force", action="store_true")
    s.set_defaults(func=cmd_app_delete)
    s = app.add_parser("data-delete")
    s.add_argument("name")
    s.add_argument("--channel")
    s.add_argument("-f", "--force", action="store_true")
    s.set_defaults(func=cmd_app_data_delete)

    # channel
    ch = sub.add_parser("channel", help="manage channels").add_subparsers(
        dest="subcommand", required=True
    )
    s = ch.add_parser("new")
    s.add_argument("app")
    s.add_argument("channel")
    s.set_defaults(func=cmd_channel_new)
    s = ch.add_parser("delete")
    s.add_argument("app")
    s.add_argument("channel")
    s.set_defaults(func=cmd_channel_delete)

    # accesskey
    ak = sub.add_parser("accesskey", help="manage access keys").add_subparsers(
        dest="subcommand", required=True
    )
    s = ak.add_parser("new")
    s.add_argument("app")
    s.add_argument("--key")
    s.add_argument("--events", help="comma-separated whitelist")
    s.set_defaults(func=cmd_accesskey_new)
    s = ak.add_parser("list")
    s.add_argument("app", nargs="?")
    s.set_defaults(func=cmd_accesskey_list)
    s = ak.add_parser("delete")
    s.add_argument("key")
    s.set_defaults(func=cmd_accesskey_delete)

    # train
    s = sub.add_parser("train", help="run a training workflow")
    s.add_argument("--engine-json", default="engine.json")
    s.add_argument("--engine-version", default="0")
    s.add_argument("--batch")
    s.add_argument("--skip-sanity-check", action="store_true")
    s.add_argument("--stop-after-read", action="store_true")
    s.add_argument("--stop-after-prepare", action="store_true")
    s.add_argument(
        "--profile", default=None, metavar="DIR",
        help="wrap the train run in jax.profiler.trace(DIR)",
    )
    s.set_defaults(func=cmd_train)

    # deploy
    s = sub.add_parser("deploy", help="serve the latest trained model")
    s.add_argument("--engine-json", default="engine.json")
    s.add_argument("--engine-version", default="0")
    s.add_argument("--ip", default="0.0.0.0")
    s.add_argument("--port", type=int, default=8000)
    s.add_argument("--feedback", action="store_true")
    s.add_argument("--event-server-url")
    s.add_argument("--access-key")
    s.add_argument(
        "--log-url", default=None,
        help="POST server log records to this collector URL (JSON lines)",
    )
    s.set_defaults(func=cmd_deploy)

    # eval: fleet-distributed spec runs + first-class records (ISSUE 20);
    # `eval run <ImportPath>` keeps the legacy single-process Evaluation
    s = sub.add_parser("eval", help="run/inspect evaluations")
    esub = s.add_subparsers(dest="eval_action", required=True)
    er = esub.add_parser(
        "run",
        help="run an EvalSpec JSON on the fleet, or a legacy Evaluation "
             "import path single-process",
    )
    er.add_argument(
        "target",
        help="EvalSpec JSON path (fleet mode) or Evaluation import path",
    )
    er.add_argument(
        "params_generator", nargs="?",
        help="import path of an EngineParamsGenerator (legacy mode)",
    )
    er.add_argument("--tenant", default=None,
                    help="tenant scope recorded on the run")
    er.add_argument("--local-workers", type=int, default=0,
                    help="spin N in-process fleet members for the run")
    er.add_argument("--timeout", type=float, default=None,
                    help="max seconds to wait for convergence")
    er.add_argument("--no-wait", action="store_true",
                    help="submit the shards and return immediately")
    er.set_defaults(func=cmd_eval)
    el = esub.add_parser("list", help="list eval runs")
    el.add_argument("--engine", default=None)
    el.add_argument("--status", default=None,
                    choices=["running", "completed", "failed"])
    el.add_argument("--tenant", default=None)
    el.set_defaults(func=cmd_eval)
    eo = esub.add_parser("show", help="one run's record + point scores")
    eo.add_argument("run_id")
    eo.set_defaults(func=cmd_eval)
    es = esub.add_parser(
        "status", help="live fan-out view: shard jobs + partial folds"
    )
    es.add_argument("run_id")
    es.set_defaults(func=cmd_eval)
    eg = esub.add_parser("gc", help="purge old terminal eval runs")
    eg.add_argument("--keep", type=int, default=None,
                    help="terminal runs to keep (default PIO_EVAL_RETENTION)")
    eg.add_argument("--now", action="store_true",
                    help="compact without the quiescence age gate")
    eg.set_defaults(func=cmd_eval)

    # tune: run the space, park the winner on the retrain spec (ISSUE 20)
    s = sub.add_parser(
        "tune",
        help="evaluate a param space and feed the winner into the "
             "periodic-retrain spec",
    )
    s.add_argument("spec", help="EvalSpec JSON path")
    s.add_argument("--tenant", default=None,
                   help="park the winner on this tenant's retrain preset")
    s.add_argument("--local-workers", type=int, default=0,
                   help="spin N in-process fleet members for the run")
    s.add_argument("--timeout", type=float, default=None,
                   help="max seconds to wait for convergence")
    s.set_defaults(func=cmd_tune)

    # eventserver
    s = sub.add_parser("eventserver", help="run the event ingestion server")
    s.add_argument("--ip", default="0.0.0.0")
    s.add_argument("--port", type=int, default=7070)
    s.add_argument("--stats", action="store_true")
    s.add_argument(
        "--log-url", default=None,
        help="POST server log records to this collector URL (JSON lines)",
    )
    s.set_defaults(func=cmd_eventserver)

    # template gallery (reference console/Template.scala:69-429)
    s = sub.add_parser("template", help="scaffold engines from built-ins")
    tsub = s.add_subparsers(dest="template_action", required=True)
    tl = tsub.add_parser("list", help="list available templates")
    tl.set_defaults(func=cmd_template)
    tg = tsub.add_parser("get", help="copy a template into a directory")
    tg.add_argument("name", help="template name (see `pio template list`)")
    tg.add_argument("directory", help="destination directory")
    tg.add_argument(
        "--package", default=None,
        help="package name for the scaffolded engine (default my_<name>)",
    )
    tg.set_defaults(func=cmd_template)

    # storage-server (client-server storage daemon; the role the
    # reference fills with an external HBase/Postgres instance)
    s = sub.add_parser(
        "storage-server",
        help="run the shared storage service for multi-process deployments",
    )
    s.add_argument("--ip", default="127.0.0.1")
    s.add_argument("--port", type=int, default=7077)
    s.add_argument("--auth-key", default=None)
    s.set_defaults(func=cmd_storage_server)

    # adminserver / dashboard
    s = sub.add_parser("adminserver", help="run the admin REST API")
    s.add_argument("--ip", default="0.0.0.0")
    s.add_argument("--port", type=int, default=7071)
    s.set_defaults(func=cmd_adminserver)
    s = sub.add_parser(
        "dashboard",
        help="run the evaluation dashboard (+ fleet monitor panels "
             "when scrape targets are configured)",
    )
    s.add_argument(
        "--monitor-targets", dest="monitor_targets", default=None,
        help="fleet scrape targets instance=url[,...] "
             "(default: PIO_MONITOR_TARGETS)",
    )
    s.add_argument("--ip", default="0.0.0.0")
    s.add_argument("--port", type=int, default=9000)
    s.set_defaults(func=cmd_dashboard)

    # status
    s = sub.add_parser("status", help="verify environment + storage")
    s.add_argument(
        "--event-url",
        help="also query a RUNNING event server's GET /segments/stats "
             "(ISSUE 14: the segmentfs admin surface) instead of only "
             "the locally-opened store",
    )
    s.add_argument(
        "--access-key",
        help="access key for --event-url (picks the app/channel whose "
             "segment stats to read)",
    )
    s.set_defaults(func=cmd_status)

    # metrics (ISSUE 1: registry exposition from the console)
    s = sub.add_parser(
        "metrics",
        help="print Prometheus metrics: this process's registry, or a "
             "running server's /metrics via --url",
    )
    s.add_argument(
        "--url", default=None,
        help="scrape this URL (e.g. http://127.0.0.1:8000/metrics) "
             "instead of the local registry",
    )
    s.add_argument(
        "--summary", action="store_true",
        help="render a human-readable summary instead of exposition text",
    )
    s.set_defaults(func=cmd_metrics)

    # trace (ISSUE 2: span traces from the console)
    s = sub.add_parser(
        "trace",
        help="inspect tail-sampled request traces (local recorder, or a "
             "running server via --url)",
    )
    tsub = s.add_subparsers(dest="trace_action", required=True)
    tl = tsub.add_parser("list", help="list retained trace summaries")
    tl.add_argument("--url", help="server base URL, e.g. http://127.0.0.1:8000")
    tl.add_argument("--limit", type=int, default=20)
    tl.add_argument("--fleet", action="store_true",
                    help="assembled cross-process traces (the fleet "
                         "collector on a gateway/dashboard/monitor)")
    tl.set_defaults(func=cmd_trace)
    ts = tsub.add_parser("show", help="print one trace's span tree")
    ts.add_argument("trace_id")
    ts.add_argument("--url", help="server base URL")
    ts.add_argument("--fleet", action="store_true",
                    help="look the trace up in the fleet collector")
    ts.set_defaults(func=cmd_trace)
    te = tsub.add_parser(
        "export",
        help="write Chrome trace-event JSON (open at ui.perfetto.dev)",
    )
    te.add_argument("trace_id", nargs="?", default=None,
                    help="one trace (default: all retained)")
    te.add_argument("--url", help="server base URL")
    te.add_argument("--fleet", action="store_true",
                    help="export assembled fleet traces")
    te.add_argument("--output", required=True)
    te.set_defaults(func=cmd_trace)
    tt = tsub.add_parser(
        "stats",
        help="where the last seconds went, by span name: count, total "
             "and self seconds of every span (no trace export needed)",
    )
    tt.add_argument("--url", help="server base URL")
    tt.add_argument("--window", type=float, default=60.0,
                    help="seconds to look back (default 60, at most 900)")
    tt.set_defaults(func=cmd_trace)

    # profile (ISSUE 3: device-profile accounting from the console)
    s = sub.add_parser(
        "profile",
        help="per-executable device profiling: XLA cost/memory analysis, "
             "MFU/roofline, padding waste (local, or a server via --url)",
    )
    psub = s.add_subparsers(dest="profile_action", required=True)
    pl = psub.add_parser("list", help="list profiled executables")
    pl.add_argument("--url", help="server base URL, e.g. http://127.0.0.1:8000")
    pl.set_defaults(func=cmd_profile)
    ps = psub.add_parser("show", help="one executable's full profile")
    ps.add_argument("name")
    ps.add_argument("--url", help="server base URL")
    ps.set_defaults(func=cmd_profile)
    pc = psub.add_parser(
        "capture",
        help="open an on-demand jax.profiler trace window (server needs "
             "PIO_PROFILE_CAPTURE_DIR set; or --dir for this process)",
    )
    pc.add_argument("--url", help="server base URL")
    pc.add_argument("--dir", help="local output directory (no --url)")
    pc.add_argument("--seconds", type=float, default=2.0)
    pc.set_defaults(func=cmd_profile)

    # faults (ISSUE 4: chaos/fault-injection admin from the console)
    s = sub.add_parser(
        "faults",
        help="fault-injection registry: list/set/clear named fault points "
             "(local, or a running server via --url — needs "
             "PIO_FAULTS_ADMIN=1 on the server)",
    )
    fsub = s.add_subparsers(dest="faults_action", required=True)
    fl = fsub.add_parser("list", help="show active fault specs")
    fl.add_argument("--url", help="server base URL, e.g. http://127.0.0.1:8000")
    fl.set_defaults(func=cmd_faults)
    fs = fsub.add_parser(
        "set", help="install fault specs: point:mode:prob[:param][,...]"
    )
    fs.add_argument(
        "spec",
        help="e.g. storage.rpc:error:0.2 or dispatch.device:delay:1.0:0.05",
    )
    fs.add_argument("--seed", type=int, default=None,
                    help="deterministic RNG seed for the fault points")
    fs.add_argument("--url", help="server base URL")
    fs.set_defaults(func=cmd_faults)
    fc = fsub.add_parser("clear", help="clear one fault point, or all")
    fc.add_argument("point", nargs="?", default=None,
                    help="fault point to clear (default: all)")
    fc.add_argument("--url", help="server base URL")
    fc.set_defaults(func=cmd_faults)

    # monitoring plane (ISSUE 8): monitor / alerts / tsdb
    s = sub.add_parser(
        "monitor",
        help="standalone fleet monitor: scrape /metrics from a target "
             "list into the TSDB and run SLO burn-rate alerting",
    )
    s.add_argument(
        "--targets", default=None,
        help="instance=url[,instance=url] (default: PIO_MONITOR_TARGETS)",
    )
    s.add_argument("--interval", type=float, default=10.0,
                   help="scrape/evaluate period in seconds")
    s.add_argument("--duration", type=float, default=None,
                   help="stop after this many seconds (default: forever)")
    s.add_argument(
        "--slos", default=None,
        help="SLO specs: JSON array or @/path.json (default: PIO_SLOS)",
    )
    s.add_argument(
        "--expr", action="append", default=None, metavar="EXPR",
        help="series-algebra expression to evaluate and print each "
             "pass (repeatable)",
    )
    s.set_defaults(func=cmd_monitor)

    s = sub.add_parser(
        "lint",
        help="run the in-tree invariant analyzer (ISSUE 12)",
    )
    s.add_argument("paths", nargs="*",
                   help="files/dirs to lint (default: the package)")
    s.add_argument("--rule", action="append", default=None,
                   help="run only this rule (repeatable)")
    s.add_argument("--json", action="store_true",
                   help="emit findings as JSON")
    s.add_argument(
        "--knobs", action="store_true",
        help="emit the env-knob registry as a markdown table",
    )
    s.add_argument(
        "--check-readme", default=None, metavar="README",
        help="with --knobs: verify the README knob table is fresh",
    )
    s.add_argument(
        "--tsan-report", nargs="?", const="tsan-report.json",
        default=None, metavar="PATH",
        help="pretty-print a sanitizer JSON report (exit 1 on findings)",
    )
    s.set_defaults(func=cmd_lint)

    s = sub.add_parser(
        "alerts",
        help="SLO alert states (local engine, or a server via --url)",
    )
    asub = s.add_subparsers(dest="alerts_action", required=True)
    al = asub.add_parser("list", help="list SLOs with their alert state")
    al.add_argument("--url", help="server base URL, e.g. http://127.0.0.1:8000")
    al.set_defaults(func=cmd_alerts)
    ao = asub.add_parser("show", help="one SLO's full status")
    ao.add_argument("name")
    ao.add_argument("--url", help="server base URL")
    ao.set_defaults(func=cmd_alerts)

    s = sub.add_parser(
        "tsdb",
        help="query the in-process time-series history (local, or a "
             "server via --url)",
    )
    dsub = s.add_subparsers(dest="tsdb_action", required=True)
    dq = dsub.add_parser(
        "query", help="list series, or one series' points/aggregates, "
                      "or evaluate a series-algebra expression"
    )
    dq.add_argument(
        "expr", nargs="?", default=None,
        help="expression to evaluate, e.g. "
             "'sum by (instance) (rate(errors_total[5m]))' "
             "(omit for the series listing / --name forms)",
    )
    dq.add_argument("--name", default=None,
                    help="series name (omit to list all)")
    dq.add_argument("--labels", default=None,
                    help="label filter, k:v[,k:v...]")
    dq.add_argument("--window", type=float, default=None,
                    help="window seconds (default: full ring)")
    dq.add_argument("--agg", choices=("rate", "increase", "quantile"),
                    default=None)
    dq.add_argument("--q", type=float, default=None,
                    help="quantile for --agg quantile (default 0.99)")
    dq.add_argument("--last", type=int, default=20,
                    help="points to print per series")
    dq.add_argument("--url", help="server base URL")
    dq.set_defaults(func=cmd_tsdb)

    # model lifecycle (ISSUE 5): jobs / models / rollout
    s = sub.add_parser(
        "jobs", help="background training job queue"
    )
    jsub = s.add_subparsers(dest="jobs_action", required=True)
    js = jsub.add_parser("submit", help="queue a train job")
    js.add_argument("--variant", default="engine.json",
                    help="engine variant JSON path (default engine.json)")
    js.add_argument("--timeout", type=float, default=None,
                    help="wall-clock train timeout in seconds")
    js.add_argument("--period", type=float, default=None,
                    help="periodic retrain interval in seconds")
    js.add_argument("--max-attempts", type=int, default=3,
                    help="infra-failure retries before the job fails")
    js.set_defaults(func=cmd_jobs)
    jl = jsub.add_parser("list", help="list train jobs")
    jl.add_argument("--status",
                    choices=("queued", "running", "completed", "failed"))
    jl.set_defaults(func=cmd_jobs)
    jo = jsub.add_parser("show", help="one job's full record")
    jo.add_argument("job_id")
    jo.set_defaults(func=cmd_jobs)
    jg = jsub.add_parser("logs", help="print a job's train log")
    jg.add_argument("job_id")
    jg.set_defaults(func=cmd_jobs)
    jj = jsub.add_parser("gc", help="purge old terminal job records")
    jj.add_argument("--keep", type=int, default=200,
                    help="completed/failed records to keep")
    jj.set_defaults(func=cmd_jobs)
    jw = jsub.add_parser(
        "worker", help="run the train scheduler worker loop"
    )
    jw.add_argument("--log-dir", default=None,
                    help="per-job log directory")
    jw.add_argument("--once", action="store_true",
                    help="drain currently-queued jobs, then exit")
    jw.set_defaults(func=cmd_jobs)

    s = sub.add_parser(
        "fleet", help="multi-worker training fleet"
    )
    fsub = s.add_subparsers(dest="fleet_action", required=True)
    fs = fsub.add_parser("status", help="live workers + queue depth")
    fs.set_defaults(func=cmd_fleet)
    fw = fsub.add_parser(
        "worker", help="run a fleet worker (CAS-claiming scheduler)"
    )
    fw.add_argument("--log-dir", default=None,
                    help="per-job log directory")
    fw.add_argument("--max-concurrent", type=int, default=None,
                    help="train subprocesses in flight at once")
    fw.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (multi-host trains)")
    fw.add_argument("--num-processes", type=int, default=1,
                    help="fleet process count (1 = single-host)")
    fw.add_argument("--process-id", type=int, default=0,
                    help="this worker's process id")
    fw.set_defaults(func=cmd_fleet)

    s = sub.add_parser(
        "gateway",
        help="replicated serving tier: L7 router with health-aware "
             "routing, hedged queries, and closed-loop autoscaling",
    )
    gsub = s.add_subparsers(dest="gateway_action", required=True)
    gr = gsub.add_parser("run", help="run the gateway process")
    gr.add_argument("--ip", default="0.0.0.0")
    gr.add_argument("--port", type=int, default=8100)
    gr.add_argument("--no-hedge", action="store_true",
                    help="disable speculative hedged queries")
    gr.add_argument("--autoscale", action="store_true",
                    help="run the autoscaler policy (decision log + "
                         "gateway_scale_events_total)")
    gr.add_argument("--min-replicas", type=int, default=1)
    gr.add_argument("--max-replicas", type=int, default=8)
    gr.set_defaults(func=cmd_gateway)
    gs = gsub.add_parser("status", help="a running gateway's fleet view")
    gs.add_argument("--url", default=None,
                    help="gateway base URL (default http://127.0.0.1:8100)")
    gs.set_defaults(func=cmd_gateway)
    gl = gsub.add_parser(
        "replicas", help="replica records in the shared registry"
    )
    gl.set_defaults(func=cmd_gateway)
    gd = gsub.add_parser(
        "drain", help="gracefully retire one replica (zero-drop)"
    )
    gd.add_argument("replica", help="replica id to drain")
    gd.add_argument("--url", default=None,
                    help="gateway base URL (default http://127.0.0.1:8100)")
    gd.set_defaults(func=cmd_gateway)

    s = sub.add_parser(
        "models", help="model version registry"
    )
    msub = s.add_subparsers(dest="models_action", required=True)
    ml = msub.add_parser("list", help="list model versions")
    ml.add_argument("--engine", help="filter by engine id")
    ml.add_argument(
        "--status",
        choices=("trained", "canary", "live", "rolled_back", "archived"),
    )
    ml.set_defaults(func=cmd_models)
    mo = msub.add_parser("show", help="one version's record + lineage")
    mo.add_argument("version_id")
    mo.set_defaults(func=cmd_models)
    mp = msub.add_parser("promote", help="mark a version live")
    mp.add_argument("version_id")
    mp.set_defaults(func=cmd_models)
    mr = msub.add_parser("rollback", help="mark a version rolled_back")
    mr.add_argument("version_id")
    mr.add_argument("--reason", default=None)
    mr.set_defaults(func=cmd_models)
    mg = msub.add_parser("gc", help="retention GC over old versions")
    mg.add_argument("--keep", type=int, default=5,
                    help="non-serving versions kept per engine variant")
    mg.add_argument("--delete-blobs", action="store_true",
                    help="also delete unreferenced MODELDATA blobs")
    mg.set_defaults(func=cmd_models)

    s = sub.add_parser(
        "tenants", help="multi-tenant serving control plane"
    )
    tnsub = s.add_subparsers(dest="tenants_action", required=True)
    tn = tnsub.add_parser("list", help="list tenants")
    tn.set_defaults(func=cmd_tenants)
    tn = tnsub.add_parser("show", help="one tenant's full record")
    tn.add_argument("tenant_id")
    tn.set_defaults(func=cmd_tenants)
    tn = tnsub.add_parser("new", help="create or update a tenant")
    tn.add_argument("tenant_id")
    tn.add_argument("--engine", required=True, help="engine id to serve")
    tn.add_argument("--engine-version", dest="engine_version", default="0")
    tn.add_argument("--variant", default=None,
                    help="engine variant (default: the engine id)")
    tn.add_argument("--weight", type=float, default=1.0,
                    help="fair-share weight in the batch scheduler")
    tn.add_argument("--qps", type=float, default=None)
    tn.add_argument("--max-concurrency", dest="max_concurrency", type=int,
                    default=None)
    tn.add_argument("--device-seconds", dest="device_seconds", type=float,
                    default=None, help="device-seconds budget per second")
    tn.add_argument("--description", default=None)
    tn.set_defaults(func=cmd_tenants)
    tn = tnsub.add_parser("set-quota", help="update fair share / quotas")
    tn.add_argument("tenant_id")
    tn.add_argument("--weight", type=float, default=None)
    tn.add_argument("--qps", type=float, default=None)
    tn.add_argument("--max-concurrency", dest="max_concurrency", type=int,
                    default=None)
    tn.add_argument("--device-seconds", dest="device_seconds", type=float,
                    default=None)
    tn.set_defaults(func=cmd_tenants)
    tn = tnsub.add_parser("delete", help="delete a tenant record")
    tn.add_argument("tenant_id")
    tn.set_defaults(func=cmd_tenants)

    s = sub.add_parser(
        "online", help="online learning: the streaming fold-in consumer"
    )
    osub = s.add_subparsers(dest="online_action", required=True)
    ost = osub.add_parser("status", help="consumer status")
    ost.add_argument("--url", default="http://localhost:8000",
                     help="query server base URL")
    ost.set_defaults(func=cmd_online)
    op = osub.add_parser("pause", help="pause fold-in (last-good serves)")
    op.add_argument("--url", default="http://localhost:8000")
    op.add_argument("--reason", default=None)
    op.set_defaults(func=cmd_online)
    orr = osub.add_parser(
        "resume", help="resume fold-in from the durable cursor"
    )
    orr.add_argument("--url", default="http://localhost:8000")
    orr.set_defaults(func=cmd_online)
    oc = osub.add_parser(
        "cursors", help="durable consumer cursor records in storage"
    )
    oc.set_defaults(func=cmd_online)

    s = sub.add_parser(
        "rollout", help="canary rollout on a running query server"
    )
    rsub = s.add_subparsers(dest="rollout_action", required=True)
    rs = rsub.add_parser("start", help="start a canary")
    rs.add_argument("--url", default="http://localhost:8000",
                    help="query server base URL")
    rs.add_argument("--version", default=None,
                    help="model version id (default: newest trained)")
    rs.add_argument("--fraction", type=float, default=None,
                    help="candidate traffic share (0..1]")
    rs.add_argument("--bake-s", dest="bake_s", type=float, default=None,
                    help="healthy seconds before auto-promote")
    rs.add_argument("--min-requests", dest="min_requests", type=int,
                    default=None, help="candidate samples before judging")
    rs.add_argument("--shadow", action="store_true",
                    help="mirror traffic instead of splitting it")
    rs.set_defaults(func=cmd_rollout)
    rt = rsub.add_parser("status", help="rollout status")
    rt.add_argument("--url", default="http://localhost:8000")
    rt.set_defaults(func=cmd_rollout)
    ra = rsub.add_parser("abort", help="abort the active canary")
    ra.add_argument("--url", default="http://localhost:8000")
    ra.add_argument("--reason", default=None)
    ra.set_defaults(func=cmd_rollout)

    # export / import
    s = sub.add_parser(
        "export", help="export events to JSON lines or parquet"
    )
    s.add_argument("--app", required=True)
    s.add_argument("--channel")
    s.add_argument("--output", required=True)
    s.add_argument(
        "--format", choices=("json", "parquet"), default="json",
        help="output codec (reference EventsToFile.scala:42 parity)",
    )
    s.set_defaults(func=cmd_export)
    s = sub.add_parser(
        "import", help="import events from JSON lines or parquet"
    )
    s.add_argument("--app", required=True)
    s.add_argument("--channel")
    s.add_argument("--input", required=True)
    s.add_argument(
        "--format", choices=("json", "parquet"), default=None,
        help="input codec (default: sniff .parquet extension, else json)",
    )
    s.set_defaults(func=cmd_import)

    return p


def main(argv: Optional[list[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    try:
        return args.func(args)
    except KeyboardInterrupt:
        return 130
    except (CommandError, OSError, ValueError, RuntimeError) as e:
        # operator-facing errors print cleanly; genuine bugs still traceback
        return _fail(str(e))


if __name__ == "__main__":
    sys.exit(main())
