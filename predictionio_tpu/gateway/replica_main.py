"""Replica subprocess entry (`python -m predictionio_tpu.gateway.replica_main`).

The in-tree replica the SubprocessReplicaManager and the chaos e2e tests
spawn. Two modes:

- ``--stub`` (tests): serves a deterministic echo engine with an
  optional straggler knob — no storage reads on the query path, no jax
  — so gateway semantics (routing, hedging, failover, drain) are
  measurable without training a model per replica,
- default: `pio deploy` semantics — loads the latest COMPLETED
  instance of ``--engine/--variant`` from shared storage and serves it.

Either way the process registers a heartbeating replica record
(storage from the standard ``PIO_STORAGE_*`` env) under a DURABLE
identity (--state-dir / --replica-id), so a kill -9 + restart rejoins
as the SAME replica — and would resume the same online cursor.
"""

from __future__ import annotations

import argparse
import datetime as _dt
import logging
import signal
import time
from typing import Any, Optional

from predictionio_tpu.data.storage.base import EngineInstance
from predictionio_tpu.data.storage.registry import Storage, StorageConfig
from predictionio_tpu.gateway.replica import ReplicaConfig, ReplicaMember
from predictionio_tpu.workflow.server import (
    EngineRuntime,
    QueryServer,
    QueryServerConfig,
    latest_completed_runtime,
)

log = logging.getLogger(__name__)


class _StubAlgo:
    """Echo algorithm: replies with the query, the replica id, and a
    deterministic straggler delay — every `slow_every`-th query sleeps
    `slow_ms` (the hedging tests' tail source)."""

    def __init__(self, replica_id: str, slow_every: int, slow_ms: float):
        self.replica_id = replica_id
        self.slow_every = slow_every
        self.slow_ms = slow_ms
        self._n = 0
        self.serving_context = None

    def predict(self, model: Any, query: Any) -> dict:
        self._n += 1
        sleep_ms = 0.0
        if isinstance(query, dict):
            sleep_ms = float(query.get("sleep_ms") or 0.0)
        if not sleep_ms and self.slow_every and (
            self._n % self.slow_every == 0
        ):
            sleep_ms = self.slow_ms
        if sleep_ms:
            time.sleep(sleep_ms / 1000.0)
        return {"echo": query, "replica": self.replica_id}


class _StubServing:
    def supplement(self, query: Any) -> Any:
        return query

    def serve(self, query: Any, predictions: list) -> Any:
        return predictions[0]


def stub_runtime(
    replica_id: str, slow_every: int = 0, slow_ms: float = 0.0
) -> EngineRuntime:
    now = _dt.datetime.now(_dt.timezone.utc)
    return EngineRuntime(
        instance=EngineInstance(
            id=f"stub-{replica_id}", status="COMPLETED",
            start_time=now, end_time=now,
            engine_id="stub", engine_version="0", engine_variant="stub",
            engine_factory="gateway.replica_main.stub",
        ),
        engine=None,
        engine_params=None,
        algorithms=[_StubAlgo(replica_id, slow_every, slow_ms)],
        models=[None],
        serving=_StubServing(),
        query_class=None,
    )


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="pio replica",
        description="One query-server replica of the gateway tier",
    )
    ap.add_argument("--ip", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--state-dir", default=None,
                    help="durable replica-identity directory")
    ap.add_argument("--replica-id", default=None,
                    help="explicit identity (overrides --state-dir)")
    ap.add_argument("--stub", action="store_true",
                    help="serve the echo stub engine (tests)")
    ap.add_argument("--slow-every", type=int, default=0,
                    help="stub: every Nth query is a straggler")
    ap.add_argument("--slow-ms", type=float, default=200.0,
                    help="stub: straggler sleep in ms")
    ap.add_argument("--engine", default=None)
    ap.add_argument("--engine-version", default="0")
    ap.add_argument("--variant", default=None)
    ap.add_argument("--serve-dtype", default="f32")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)

    storage = Storage(StorageConfig.from_env())
    if args.stub:
        # identity has to exist before the runtime so the stub can echo
        # it; ReplicaConfig resolves the same way below
        from predictionio_tpu.gateway.identity import replica_identity

        rid = args.replica_id or replica_identity(
            args.state_dir or "~/.predictionio_tpu/replica"
        )
        runtime = stub_runtime(rid, args.slow_every, args.slow_ms)
        engines = ["stub"]
    else:
        if not args.engine:
            ap.error("--engine is required without --stub")
        rid = args.replica_id
        runtime = latest_completed_runtime(
            storage, args.engine, args.engine_version,
            args.variant or args.engine,
        )
        engines = [args.engine]

    server = QueryServer(
        storage, runtime,
        QueryServerConfig(ip=args.ip, port=args.port,
                          micro_batch=not args.stub),
    )
    port = server.start()
    member = ReplicaMember(storage, server, ReplicaConfig(
        state_dir=args.state_dir or "~/.predictionio_tpu/replica",
        replica_id=rid,
        url=f"http://{args.ip if args.ip != '0.0.0.0' else '127.0.0.1'}"
            f":{port}",
        engines=engines,
        serve_dtype=args.serve_dtype,
    ))
    server.attach_replica(member)
    log.info(
        "replica %s serving on :%d", member.replica_id, port
    )

    def _term(_sig, _frm):
        # graceful: drain (zero-drop) — the drain thread stops the
        # server, which unblocks serve_forever's join below
        if not member.drain():
            server.stop()

    signal.signal(signal.SIGTERM, _term)
    try:
        server._thread.join()  # noqa: SLF001 — the serve loop
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
