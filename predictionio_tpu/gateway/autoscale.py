"""Closed-loop autoscaling (ISSUE 15 tentpole part 4).

The monitoring plane measures, the gateway routes — this module closes
the loop: an :class:`Autoscaler` policy object consumes the SLO
engine's burn rate and the gateway's per-replica concurrency every
evaluation pass and emits **spawn/drain decisions** against a
:class:`ReplicaManager`. Policy and actuation are deliberately split:
the in-tree :class:`SubprocessReplicaManager` spawns replica
subprocesses for tests, a production deployment
plugs a k8s/ASG-shaped manager into the same three-method seam —
either way every decision lands in the bounded decision log and on
``gateway_scale_events_total{action}``, so "why did the fleet grow at
3am" is answerable from /gateway/status alone.

Scale-up triggers (either):
- SLO burn: the fast-window burn rate of any tracked SLO is at or over
  ``scale_up_burn`` — the fleet is eating error budget page-fast,
- load: mean in-flight per routable replica exceeds
  ``target_inflight`` — saturation is coming even if the SLO holds.

Scale-down requires BOTH quiet burn and mean load under
``scale_down_fraction × target_inflight``, and drains (graceful,
zero-drop) rather than kills. A cooldown between actions stops the
loop hunting; min/max bounds are hard rails.
"""

from __future__ import annotations

import logging
import subprocess
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

log = logging.getLogger(__name__)


@dataclass
class AutoscalerConfig:
    min_replicas: int = 1
    max_replicas: int = 8
    # mean in-flight queries per replica that means "saturating"
    target_inflight: float = 32.0
    # scale down only below this fraction of target (hysteresis band)
    scale_down_fraction: float = 0.25
    # fast-window burn rate that forces a scale-up (SLO page threshold)
    scale_up_burn: float = 14.4
    cooldown_s: float = 30.0
    # the min-floor rule ignores the full cooldown (a crashed fleet must
    # recover fast) but still waits this long after its own last spawn —
    # a replica takes a few seconds to boot and register, and re-firing
    # every evaluation pass until it shows up is a process storm
    floor_boot_grace_s: float = 5.0
    decision_log_size: int = 64


class ReplicaManager:
    """Actuation seam: how replicas come and go. Implementations must
    be idempotent-tolerant — the policy may re-decide during slow
    boots (the cooldown is the main guard, this is the backstop)."""

    def spawn(self) -> Optional[str]:
        """Start one replica; returns an opaque handle/id or None."""
        raise NotImplementedError

    def drain(self, replica_id: str, url: str) -> bool:
        """Begin graceful drain of one replica (zero-drop retirement)."""
        raise NotImplementedError

    def stop(self) -> None:
        """Release manager resources (kill test children etc.)."""


class SubprocessReplicaManager(ReplicaManager):
    """In-tree manager for tests: replicas are local
    ``gateway.replica_main`` subprocesses built from an argv template.
    Every ``{n}`` in a template arg is replaced with a per-spawn
    sequence number, so templated ``--replica-id r{n}`` /
    ``--state-dir .../s{n}`` args give each child its own durable
    identity; a template naming NEITHER flag gets a unique
    ``--state-dir`` appended — N children sharing replica_main's
    default state dir would collapse into ONE registry record and the
    min-floor rule would spawn forever chasing a count that never
    rises. `drain` POSTs the replica's own /replica/drain (the replica
    exits once drained)."""

    def __init__(self, argv_template: list[str], env: Optional[dict] = None):
        self.argv_template = list(argv_template)
        self.env = env
        self._lock = threading.Lock()
        self._children: list[subprocess.Popen] = []  # guarded-by: _lock
        self._seq = 0  # guarded-by: _lock
        self._auto_state_base: Optional[str] = None  # guarded-by: _lock

    def spawn(self) -> Optional[str]:
        import os
        import tempfile

        with self._lock:
            self._seq += 1
            seq = self._seq
            argv = [a.replace("{n}", str(seq)) for a in self.argv_template]
            if (
                "--replica-id" not in argv and "--state-dir" not in argv
            ):
                if self._auto_state_base is None:
                    self._auto_state_base = tempfile.mkdtemp(
                        prefix="pio-autoscale-"
                    )
                argv += ["--state-dir", os.path.join(
                    self._auto_state_base, f"replica-{seq}"
                )]
        proc = subprocess.Popen(
            [sys.executable, *argv],
            env=self.env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        with self._lock:
            self._children.append(proc)
        return f"pid:{proc.pid}"

    def drain(self, replica_id: str, url: str) -> bool:
        import urllib.request

        try:
            req = urllib.request.Request(
                url.rstrip("/") + "/replica/drain",
                data=b"{}",
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=5):
                pass
            return True
        except Exception:
            log.warning("drain request to %s failed", url, exc_info=True)
            return False

    def stop(self) -> None:
        with self._lock:
            children, self._children = self._children, []
        for proc in children:
            try:
                proc.kill()
                proc.wait(timeout=10)
            except Exception:
                pass


@dataclass
class ScaleDecision:
    action: str  # spawn | drain | hold
    reason: str
    at: float
    replicas: int
    mean_inflight: float
    burn: Optional[float]
    target: Optional[str] = None  # drained replica id, spawn handle

    def to_dict(self) -> dict[str, Any]:
        return {
            "action": self.action, "reason": self.reason,
            "at": self.at, "replicas": self.replicas,
            "mean_inflight": round(self.mean_inflight, 2),
            "burn": None if self.burn is None else round(self.burn, 3),
            "target": self.target,
        }


class Autoscaler:
    """Pure-ish policy: `evaluate()` maps one signal snapshot to at
    most one action through the manager. The gateway's sync loop calls
    it; tests call it directly with synthetic signals."""

    def __init__(
        self,
        manager: Optional[ReplicaManager],
        config: Optional[AutoscalerConfig] = None,
        registry=None,
        clock=time.monotonic,
    ):
        self.manager = manager
        self.config = config or AutoscalerConfig()
        self._clock = clock
        self._last_action_at: Optional[float] = None
        self._last_spawn_at: Optional[float] = None
        self.decisions: deque[ScaleDecision] = deque(
            maxlen=self.config.decision_log_size
        )
        if registry is None:
            from predictionio_tpu.obs.registry import get_default_registry

            registry = get_default_registry()
        self._events = registry.counter(
            "gateway_scale_events_total",
            "autoscaler actions taken, by action",
            ("action",),  # label-bound: literal spawn|drain
        )

    # -- policy ------------------------------------------------------------
    def evaluate(
        self,
        replicas: int,
        mean_inflight: float,
        burn: Optional[float],
        drain_candidate: Optional[tuple[str, str]] = None,
    ) -> Optional[ScaleDecision]:
        """One pass: `replicas` routable now, their mean in-flight
        load, the worst tracked fast-window burn rate (None = no SLO
        signal), and the (id, url) the gateway would drain first (its
        least-loaded replica). Returns the decision taken, or None."""
        cfg = self.config
        now = self._clock()
        in_cooldown = (
            self._last_action_at is not None
            and now - self._last_action_at < cfg.cooldown_s
        )

        def act(action: str, reason: str, target: Optional[str]) -> ScaleDecision:
            d = ScaleDecision(
                action=action, reason=reason, at=time.time(),
                replicas=replicas, mean_inflight=mean_inflight,
                burn=burn, target=target,
            )
            self.decisions.append(d)
            self._events.inc(action=action)
            self._last_action_at = now
            if action == "spawn":
                self._last_spawn_at = now
            log.info("autoscaler %s: %s", action, reason)
            return d

        # hard rail first: below the floor, spawn regardless of the
        # FULL cooldown (a crashed fleet must not wait out 30 s to
        # recover) — but give our own last spawn a boot grace, or a
        # replica that takes seconds to register draws one sibling per
        # evaluation pass
        if replicas < cfg.min_replicas:
            if (
                self._last_spawn_at is not None
                and now - self._last_spawn_at < cfg.floor_boot_grace_s
            ):
                return None
            target = self.manager.spawn() if self.manager else None
            return act(
                "spawn",
                f"{replicas} routable < min_replicas {cfg.min_replicas}",
                target,
            )
        if in_cooldown:
            return None
        burning = burn is not None and burn >= cfg.scale_up_burn
        saturated = mean_inflight >= cfg.target_inflight
        if (burning or saturated) and replicas < cfg.max_replicas:
            reason = (
                f"burn {burn:.1f} >= {cfg.scale_up_burn}" if burning
                else f"mean inflight {mean_inflight:.1f} >= "
                     f"{cfg.target_inflight}"
            )
            target = self.manager.spawn() if self.manager else None
            return act("spawn", reason, target)
        idle = (
            mean_inflight < cfg.scale_down_fraction * cfg.target_inflight
        )
        if (
            idle and not burning and replicas > cfg.min_replicas
            and drain_candidate is not None
        ):
            rid, url = drain_candidate
            ok = (
                self.manager.drain(rid, url) if self.manager else True
            )
            if ok:
                return act(
                    "drain",
                    f"mean inflight {mean_inflight:.1f} < "
                    f"{cfg.scale_down_fraction:.2f}x target",
                    rid,
                )
        return None

    def status(self) -> dict[str, Any]:
        return {
            "config": {
                "min_replicas": self.config.min_replicas,
                "max_replicas": self.config.max_replicas,
                "target_inflight": self.config.target_inflight,
                "scale_up_burn": self.config.scale_up_burn,
                "cooldown_s": self.config.cooldown_s,
            },
            "decisions": [d.to_dict() for d in self.decisions],
        }
