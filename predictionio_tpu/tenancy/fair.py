"""Weighted-fair queueing for the micro-batch dispatcher (deficit round
robin).

The dispatcher's single FIFO is exactly how one hog tenant starves
everyone: 500 queued hog queries mean every other tenant's query waits
500 device slots. `FairQueue` replaces the FIFO with one sub-queue per
tenant drained by **deficit round robin** — each visit to a tenant adds
its ``weight`` to a per-tenant deficit counter and serves queries while
the deficit covers them (every query costs 1), so over any window each
backlogged tenant receives device slots proportional to its weight no
matter how deep another tenant's backlog is.

API-compatible with the subset of ``queue.Queue`` the dispatcher's drain
loop uses (``put`` / ``get(timeout=)`` / ``get_nowait`` raising
``queue.Empty``), so the dispatcher needs no control-flow changes — and
with a single (or no) tenant active, DRR degenerates to plain FIFO, so
the single-tenant path pays only a dict lookup.
"""

from __future__ import annotations

import collections
import queue as _q
import threading
import time
from typing import Any, Callable, Optional

# a visit can accumulate at most this much deficit — bounds the burst a
# long-idle tenant can claim in one round (standard DRR quantum cap)
_MAX_DEFICIT = 64.0


class FairQueue:
    """Thread-safe DRR queue over items carrying a ``tenant`` attribute
    (``None`` = the default/untenanted stream, weight 1)."""

    def __init__(
        self,
        weight_of: Optional[Callable[[Optional[str]], float]] = None,
    ):
        self._weight_of = weight_of
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._queues: dict[Optional[str], collections.deque] = {}  # guarded-by: _lock|_not_empty
        self._deficit: dict[Optional[str], float] = {}  # guarded-by: _lock|_not_empty
        # round-robin rotation of tenants with queued items
        self._order: collections.deque = collections.deque()  # guarded-by: _lock|_not_empty
        self._size = 0  # guarded-by: _lock|_not_empty

    def _weight(self, tenant: Optional[str]) -> float:
        if self._weight_of is None:
            return 1.0
        try:
            w = float(self._weight_of(tenant))
        except Exception:
            return 1.0
        return w if w > 0 else 1.0

    def put(self, item: Any) -> None:
        tenant = getattr(item, "tenant", None)
        with self._not_empty:
            dq = self._queues.get(tenant)
            if dq is None:
                dq = self._queues[tenant] = collections.deque()
                self._deficit.setdefault(tenant, 0.0)
                self._order.append(tenant)
            dq.append(item)
            self._size += 1
            self._not_empty.notify()

    def qsize(self) -> int:
        with self._lock:
            return self._size

    def depths(self) -> dict[str, int]:
        """Per-tenant queued depth (status/debug surface)."""
        with self._lock:
            return {
                ("(default)" if t is None else t): len(dq)
                for t, dq in self._queues.items()
                if dq
            }

    def backlogged(self) -> set:
        """Raw tenant keys (None = untenanted) with queued items — the
        dispatcher's tenant-aware drain reads this per linger pass
        (ISSUE 11 satellite)."""
        with self._lock:
            return {t for t, dq in self._queues.items() if dq}

    def get_nowait(self, skip: Optional[set] = None) -> Any:
        with self._lock:
            return self._pop_locked(skip)

    def get(
        self, timeout: Optional[float] = None,
        skip: Optional[set] = None,
        unless: Optional[Callable[[], bool]] = None,
    ) -> Any:
        """Pop the next item by DRR. `skip` (ISSUE 14 satellite —
        continuous-batching admission caps) names tenant keys whose
        items must stay queued this call: when every backlogged tenant
        is skipped the call behaves as empty, so the dispatcher's
        assembling bucket keeps room for the un-capped tenants'
        arrivals instead of filling with one tenant's backlog.

        `unless` (ISSUE 38) is the caller's other reason to stop
        waiting: with nothing to pop and `unless()` true the call
        raises ``queue.Empty`` as at its timeout. It is read under the
        queue's lock, before the first wait and after every `wake()`,
        so whoever changes what it reads and THEN calls `wake()` is
        never missed."""
        deadline = (
            None if timeout is None else time.monotonic() + timeout
        )
        with self._not_empty:
            while True:
                if self._size:
                    try:
                        return self._pop_locked(skip)
                    except _q.Empty:
                        pass  # only skipped tenants queued: wait
                if unless is not None and unless():
                    raise _q.Empty
                if deadline is None:
                    self._not_empty.wait()
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise _q.Empty
                    self._not_empty.wait(remaining)

    def wake(self) -> None:
        """Wake a blocked `get` without an item, to read its `unless`
        again: the condition an arrival notifies is the one the
        consumer sleeps on, so one wait serves both events."""
        with self._not_empty:
            self._not_empty.notify_all()

    def _pop_locked(self, skip: Optional[set] = None) -> Any:  # lint: holds=_not_empty
        if not self._size:
            raise _q.Empty
        if skip and all(
            (t in skip) or not dq for t, dq in self._queues.items()
        ):
            # nothing servable outside the skip set — progress below
            # would otherwise spin on skip-rotations forever
            raise _q.Empty
        # DRR: visit the head tenant; a visit credits `weight`, serving
        # one item debits 1. Progress is guaranteed — every full
        # rotation credits each backlogged tenant at least min-weight,
        # so some deficit crosses 1 within ceil(1/min_weight) rotations
        # (skipped tenants rotate past without credit: an admission cap
        # must not bank DRR priority for the capped tenant).
        while True:
            tenant = self._order[0]
            dq = self._queues.get(tenant)
            if not dq:
                # drained earlier: drop from the rotation (deficit does
                # not accrue while idle — an idle tenant must not bank
                # priority for later)
                self._order.popleft()
                self._queues.pop(tenant, None)
                self._deficit.pop(tenant, None)
                continue
            if skip and tenant in skip:
                self._order.rotate(-1)
                continue
            deficit = self._deficit[tenant]
            if deficit < 1.0:
                deficit = min(
                    deficit + self._weight(tenant), _MAX_DEFICIT
                )
                self._deficit[tenant] = deficit
            if deficit >= 1.0:
                self._deficit[tenant] = deficit - 1.0
                item = dq.popleft()
                self._size -= 1
                if not dq:
                    self._order.popleft()
                    self._queues.pop(tenant, None)
                    self._deficit.pop(tenant, None)
                elif self._deficit[tenant] < 1.0:
                    # spent this visit's credit: next tenant's turn
                    self._order.rotate(-1)
                return item
            # weight < 1 and credit still short: rotate, credit persists
            self._order.rotate(-1)
