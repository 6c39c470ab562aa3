"""Driver `http_open_loop_sharded`: the client's wait on a catalogue that no
single chip holds. `http_open_loop`'s set-up, window and comparison as they
are, over a model whose configuration asks for the sharded tier
(`"shard_serving": true`: `fleet.ShardedRuntime`, one shard a chip); on top:

- a rehearsal forces four host devices, so the tiny run crosses shards too;
- `check` adds `sharded_tier_inactive` (limit 0): the model's
  `sharded_runtime()` is live with as many shards as the cell has chips, so
  a silent fall-back to one device can never be timed;
- the window carries what the server's registry counted of the sharded
  tier's batches and exclusion bytes, for the per-layer readers. A program
  without those counters gives none, and the readers leave the metric out.
"""

from __future__ import annotations

import os
import re

from benchmarks import serving
from benchmarks.drivers import http_open_loop
from benchmarks.harness import Check, Context

SHARDS_IN_REHEARSAL = 4
_COUNT_FLAG = "--xla_force_host_platform_device_count"


def rehearsal_env() -> dict:
    """`serving.rehearsal_env()` and four host devices: whatever count the
    caller's XLA_FLAGS pins (tests/benchmarks pins one) is replaced, its
    other flags stay."""
    rest = re.sub(rf"{_COUNT_FLAG}=\d+", "", os.environ.get("XLA_FLAGS", ""))
    # first, where the pinned count stood: what follows a token that is no
    # flag (the tests' `intra_op_parallelism_threads=2`) is not read
    flags = f"{_COUNT_FLAG}={SHARDS_IN_REHEARSAL} {rest.strip()}".strip()
    return dict(serving.rehearsal_env(), XLA_FLAGS=flags)


setup = serving.setup


def sharded_counts(server) -> dict | None:
    batches = _family(server, "sharded_batches_total")
    nbytes = _family(server, "sharded_exclusion_bytes_total")
    if batches is None or nbytes is None:
        return None
    return {"batches": float(batches.total), "bytes": float(nbytes.total),
            "by_form": {form: float(batches.value(form=form))
                        for form in ("none", "rows", "mask")}}


def _family(server, name: str):
    return next((f for f in server.metrics.families() if f.name == name), None)


def window(session: dict, ctx: Context) -> dict:
    before = sharded_counts(session["server"])
    win = http_open_loop.window(session, ctx)
    after = sharded_counts(session["server"])
    if before is not None and after is not None:
        win["sharded_batches"] = after["batches"] - before["batches"]
        win["sharded_exclusion_bytes"] = after["bytes"] - before["bytes"]
        win["notes"]["sharded_batches_by_form"] = {
            form: after["by_form"][form] - before["by_form"][form]
            for form in after["by_form"]}
    return win


def shards_live(session: dict) -> int:
    """Shards of the model's live sharded runtime; 0 where it has none."""
    model = session.get("model")
    info = model.sharded_info() if model is not None else None
    return int(info["shards"]) if info else 0


def check(session: dict, ctx: Context, win: dict) -> list[Check]:
    want = (SHARDS_IN_REHEARSAL if ctx.rehearsal
            else int(ctx.plan.cell["chips"]))
    inactive = float(shards_live(session) != want)
    return serving.check(session, ctx, win) + [
        Check("sharded_tier_inactive", inactive, 0.0)]


teardown = serving.teardown


def prove(ctx: Context, controls: bool) -> dict:
    return serving.prove(ctx, controls, window)
