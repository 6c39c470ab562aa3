"""Durable long-horizon TSDB (ISSUE 18): the columnar block format,
WAL flush/seal/replay, restart-boundary counter math (increase/rate
across a kill -9 with no phantom reset), downsampling compaction with
the documented edge-bucket bound, per-tier retention that never
outruns the next tier's watermark, tier selection for long windows,
multi-window burn-rate SLO specs, and the Monitor/console wiring."""

import os
import signal
import subprocess
import sys
import time

import pytest

from predictionio_tpu.obs.monitor.compact import (
    DEFAULT_RETENTION,
    Compactor,
)
from predictionio_tpu.obs.monitor.durable import (
    BlockHandle,
    DurableTSDB,
    TIER_BUCKETS,
    write_block,
)
from predictionio_tpu.obs.monitor.slo import SLOEngine, SLOSpec
from predictionio_tpu.obs.monitor.tsdb import TSDB

T0 = 1_700_000_000.0

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(TESTS_DIR)


def _mk(tmp_path, **kw):
    """A DurableTSDB with background flushing effectively disabled —
    tests drive flush_once()/seal explicitly."""
    kw.setdefault("capacity", 720)
    kw.setdefault("flush_interval_s", 9999.0)
    kw.setdefault("seal_age_s", 9999.0)
    return DurableTSDB(str(tmp_path / "tsdb"), **kw)


def _walk(db, name, labels, start, end, step, rate, kind="counter",
          v0=0.0):
    """Write a counter climbing `rate` per point every `step` s;
    returns the final value."""
    v = v0
    t = start
    while t <= end:
        v += rate
        db.add(name, labels, v, kind, t)
        t += step
    return v


# ---------------------------------------------------------------------------
# block format
# ---------------------------------------------------------------------------


class TestBlockFormat:
    def test_roundtrip(self, tmp_path):
        path = str(tmp_path / "b-1-2-w00000001.blk")
        ts = [int((T0 + i * 10) * 1000) for i in range(50)]
        vals = [float(i) * 1.5 for i in range(50)]
        rows = [("m", (("a", "1"),), "counter", ts, {"v": vals})]
        footer = write_block(path, "raw", rows)
        assert footer is not None
        h = BlockHandle.load(path)
        got_ts, cols = h.read_series(("m", (("a", "1"),)))
        assert got_ts == pytest.approx([t / 1000.0 for t in ts])
        assert cols["v"] == pytest.approx(vals)
        assert h.read_series(("missing", ())) is None

    def test_corrupt_block_is_skipped_not_fatal(self, tmp_path):
        db = _mk(tmp_path)
        _walk(db, "c", {}, T0, T0 + 600, 10.0, 1.0)
        db.flush_once(seal=True)
        blocks = db.tiers["raw"].blocks()
        assert blocks
        # truncate one block mid-body: the index must drop it and
        # queries must keep answering from memory
        with open(blocks[0].path, "r+b") as f:
            f.truncate(10)
        db.tiers["raw"].invalidate()
        assert db.tiers["raw"].blocks() == []
        assert db.summary()["durable"]["tiers"]["raw"]["blocks"] == 0
        db.stop()


# ---------------------------------------------------------------------------
# WAL + replay: the restart boundary
# ---------------------------------------------------------------------------


class TestRestartBoundary:
    def test_replay_restores_history(self, tmp_path):
        db = _mk(tmp_path)
        _walk(db, "reqs", {"code": "200"}, T0, T0 + 3600, 10.0, 4.0)
        db.flush_once(seal=True)
        db.stop()
        db2 = _mk(tmp_path)
        assert db2.replayed_points > 0
        s = db2.matching("reqs", {"code": "200"})
        assert len(s) == 1
        now = T0 + 3600
        pts = db2.points(s[0], 3600.0, now)
        assert len(pts) >= 300
        db2.stop()

    def test_increase_across_restart_matches_no_restart(self, tmp_path):
        """The acceptance criterion: increase()/rate() over a window
        straddling the restart equal the uninterrupted values — no
        phantom reset at the boundary."""
        now = T0 + 7200
        ref = TSDB(capacity=4096)
        db = _mk(tmp_path)
        for target in (ref, db):
            _walk(target, "reqs", {}, T0, T0 + 3600, 10.0, 4.0)
        db.flush_once(seal=True)
        db.stop()
        db2 = _mk(tmp_path)
        # post-restart traffic continues the SAME counter (a monitor
        # restart, not a process restart of the counted service)
        for target in (ref, db2):
            _walk(target, "reqs", {}, T0 + 3610, now, 10.0, 4.0,
                  v0=4.0 * 361)
        rs = ref.matching("reqs", None)[0]
        ds = db2.matching("reqs", None)[0]
        for window in (1800.0, 3600.0, 7200.0):
            want = ref.series_increase(rs, window, now)
            got = db2.series_increase(ds, window, now)
            assert got == pytest.approx(want, abs=1e-6), (
                f"window={window}: {got} != {want}"
            )
        db2.stop()

    def test_genuine_reset_inside_window_still_detected(self, tmp_path):
        """A real counter restart (value drops to ~0) inside a window
        that also straddles the monitor restart must still count the
        post-reset accumulation — reset-awareness survives tiering."""
        now = T0 + 7200
        db = _mk(tmp_path)
        _walk(db, "c", {}, T0, T0 + 3600, 10.0, 1.0)  # → 361
        db.flush_once(seal=True)
        db.stop()
        db2 = _mk(tmp_path)
        # the counted process restarts: counter starts over from 0
        _walk(db2, "c", {}, T0 + 3610, now, 10.0, 1.0, v0=0.0)
        s = db2.matching("c", None)[0]
        got = db2.series_increase(s, 7200.0, now)
        # 361 pre-restart + 360 post-reset accumulation
        assert got == pytest.approx(361 + 360, abs=1.0)
        db2.stop()

    def test_unsealed_wal_tail_replays(self, tmp_path):
        """Points flushed to the WAL but never sealed into a block
        (the kill -9 shape) still come back."""
        db = _mk(tmp_path)
        _walk(db, "g", {}, T0, T0 + 100, 10.0, 1.0, kind="gauge")
        db.flush_once(seal=False)  # WAL only, no block
        # no stop(): simulate an abrupt death
        assert db.tiers["raw"].blocks() == []
        db2 = _mk(tmp_path)
        s = db2.matching("g", None)
        assert s and len(db2.points(s[0], 3600.0, T0 + 100)) == 11
        db2.stop()
        db._stop.set()  # silence the leak tripwire for the orphan

    def test_kill9_subprocess_history_survives(self, tmp_path):
        """End-to-end: a separate process seeds the durable dir, dies
        by SIGKILL mid-flight, and a fresh process (the `pio tsdb
        query` shape) reads the pre-kill history."""
        d = str(tmp_path / "tsdb")
        script = f"""
import os, signal
from predictionio_tpu.obs.monitor.durable import DurableTSDB
db = DurableTSDB({d!r}, flush_interval_s=9999, seal_age_s=9999)
v = 0.0
for i in range(361):
    v += 4.0
    db.add("reqs", {{}}, v, "counter", {T0} + i * 10.0)
db.flush_once()
os.kill(os.getpid(), signal.SIGKILL)
"""
        proc = subprocess.run(
            [sys.executable, "-c", script], cwd=REPO_DIR,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            timeout=120,
        )
        assert proc.returncode == -signal.SIGKILL
        db = _mk(tmp_path)
        s = db.matching("reqs", None)
        assert s, "pre-kill history lost"
        inc = db.series_increase(s[0], 3600.0, T0 + 3600)
        assert inc == pytest.approx(4.0 * 360, abs=4.0)
        db.stop()


# ---------------------------------------------------------------------------
# downsampling compaction
# ---------------------------------------------------------------------------


class TestCompaction:
    def _seeded(self, tmp_path, hours=8.0, step=30.0, rate=3.0):
        db = _mk(tmp_path, capacity=120)
        now = T0 + hours * 3600
        _walk(db, "reqs", {}, T0, now, step, rate)
        db.flush_once(seal=True)
        return db, now

    def test_downsample_produces_tiers(self, tmp_path):
        db, now = self._seeded(tmp_path)
        comp = Compactor(db, interval_s=9999.0)
        res = comp.run_once(now=now, force=True)
        assert res["buckets"] > 0
        assert db.tiers["5m"].stats()["blocks"] >= 1
        assert db.tiers["1h"].stats()["blocks"] >= 1
        assert comp.stats()["compacted_blocks"] >= 2
        db.stop()

    def test_downsampled_increase_within_edge_bound(self, tmp_path):
        """Documented bound: an increase answered from a downsampled
        tier may miss/add at most one bucket's worth of counter travel
        per window edge."""
        db, now = self._seeded(tmp_path, step=30.0, rate=3.0)
        Compactor(db, interval_s=9999.0).run_once(now=now, force=True)
        s = db.matching("reqs", None)[0]
        per_s = 3.0 / 30.0
        for window in (6 * 3600.0, 8 * 3600.0):
            tier = db._pick_tier(window, now - window)
            bucket = TIER_BUCKETS[tier]
            want = per_s * min(window, 8 * 3600.0)
            got = db.series_increase(s, window, now)
            # documented bound: one partial bucket of slop per edge
            assert abs(got - want) <= 2 * bucket * per_s + 3.0, (
                f"window={window} tier={tier}: {got} vs {want}"
            )
        db.stop()

    def test_raw_and_downsampled_agree(self, tmp_path):
        """Before retention prunes raw, the same window answered from
        raw points and from 5m buckets agree within the bound."""
        db, now = self._seeded(tmp_path, hours=3.0)
        Compactor(db, interval_s=9999.0).run_once(now=now, force=True)
        key = ("reqs", ())
        window = 2 * 3600.0
        cutoff, edge = now - window, now
        raw_inc, _ = db._disk_increase(key, cutoff, edge, window,
                                       tier="raw")
        ds_inc, _ = db._disk_increase(key, cutoff, edge, window,
                                      tier="5m")
        assert ds_inc == pytest.approx(raw_inc, abs=2 * 300.0 * 0.1 + 1)
        db.stop()

    def test_retention_waits_for_downsampling(self, tmp_path):
        """Raw blocks older than retention survive until the 5m tier's
        watermark passes them — pruning never eats unrolled data."""
        db, now = self._seeded(tmp_path, hours=2.0)
        raw_before = db.tiers["raw"].stats()["blocks"]
        assert raw_before > 0
        comp = Compactor(db, interval_s=9999.0,
                         retention={"raw": 0.001})
        # force=False + huge grace: nothing downsampled yet, so nothing
        # may be pruned either
        comp.grace_s = 1e9
        comp.run_once(now=now)
        assert db.tiers["raw"].stats()["blocks"] == raw_before
        # now roll up, then retention may prune rolled raw blocks
        # (a beat later, so the newest point has aged past retention)
        comp.grace_s = 0.0
        comp.run_once(now=now, force=True)
        comp.run_once(now=now + 60.0)
        assert db.tiers["raw"].stats()["blocks"] < raw_before
        db.stop()

    def test_default_retention_ordering(self):
        assert DEFAULT_RETENTION["raw"] < DEFAULT_RETENTION["5m"]
        assert DEFAULT_RETENTION["5m"] < DEFAULT_RETENTION["1h"]

    def test_compactor_thread_lifecycle(self, tmp_path):
        import threading

        db = _mk(tmp_path)
        comp = Compactor(db, interval_s=9999.0)
        comp.start()
        assert any(
            t.name == "tsdb-compactor" for t in threading.enumerate()
        )
        comp.stop()
        assert not any(
            t.name == "tsdb-compactor" for t in threading.enumerate()
        )
        db.stop()


# ---------------------------------------------------------------------------
# tier selection + long-window queries
# ---------------------------------------------------------------------------


class TestTierSelection:
    def test_long_window_picks_coarse_tier(self, tmp_path):
        db = _mk(tmp_path, capacity=60)
        now = T0 + 3 * 86400
        _walk(db, "reqs", {}, T0, now, 300.0, 30.0)
        db.flush_once(seal=True)
        Compactor(db, interval_s=9999.0).run_once(now=now, force=True)
        assert db._pick_tier(3 * 86400.0, now - 3 * 86400.0) == "1h"
        assert db._pick_tier(2 * 3600.0, now - 2 * 3600.0) in ("raw",
                                                               "5m")
        s = db.matching("reqs", None)[0]
        want = (30.0 / 300.0) * 3 * 86400
        got = db.series_increase(s, 3 * 86400.0, now)
        assert got == pytest.approx(want, rel=0.02)
        db.stop()

    def test_three_day_query_latency(self, tmp_path):
        """Acceptance shape: p50 of a 3-day increase query must
        be far under 100ms once tiered."""
        db = _mk(tmp_path, capacity=60)
        now = T0 + 3 * 86400
        _walk(db, "reqs", {}, T0, now, 300.0, 30.0)
        db.flush_once(seal=True)
        Compactor(db, interval_s=9999.0).run_once(now=now, force=True)
        s = db.matching("reqs", None)[0]
        times = []
        for _ in range(20):
            t0 = time.perf_counter()
            db.series_increase(s, 3 * 86400.0, now)
            times.append(time.perf_counter() - t0)
        times.sort()
        assert times[len(times) // 2] < 0.1
        db.stop()


# ---------------------------------------------------------------------------
# multi-window burn-rate SLOs
# ---------------------------------------------------------------------------


def _burn_spec(**kw):
    kw.setdefault("name", "api")
    kw.setdefault("kind", "expr")
    kw.setdefault(
        "expr",
        "sum(increase(errs[$window])) / sum(increase(reqs[$window]))",
    )
    kw.setdefault("objective", 0.99)
    kw.setdefault("window_s", 3600.0)
    kw.setdefault("fast_window_s", 300.0)
    kw.setdefault("burn_threshold", 2.0)
    return SLOSpec(**kw)


class TestMultiWindowSLO:
    def test_extra_pairs_normalize_and_roundtrip(self):
        spec = _burn_spec(extra_pairs=(
            {"fast_window_s": 21600.0, "window_s": 259200.0,
             "burn_threshold": 1.0},
            (1800, 21600, 1.5),
        ))
        assert spec.burn_pairs == (
            (300.0, 3600.0, 2.0),
            (21600.0, 259200.0, 1.0),
            (1800.0, 21600.0, 1.5),
        )
        again = SLOSpec.from_dict(spec.to_dict())
        assert again.burn_pairs == spec.burn_pairs

    def test_extra_pairs_validation(self):
        with pytest.raises(ValueError):
            _burn_spec(extra_pairs=((3600.0, 300.0, 1.0),))  # fast>slow
        with pytest.raises(ValueError):
            _burn_spec(extra_pairs=((0.0, 300.0, 1.0),))
        with pytest.raises(ValueError):
            _burn_spec(extra_pairs=({"nope": 1},))

    def test_six_hour_pair_fires_from_replayed_burn(self, tmp_path):
        """The acceptance criterion: after a restart the fast 5m/1h
        pair is empty, but the 6h/3d ladder pair reads the replayed
        disk tier and fires."""
        spec = _burn_spec(extra_pairs=(
            {"fast_window_s": 21600.0, "window_s": 259200.0,
             "burn_threshold": 1.0},
        ))
        now = T0 + 3 * 86400
        db = _mk(tmp_path)
        total = err = 0.0
        t = now - 3 * 86400
        while t < now - 2 * 3600:  # silence for the last 2h
            total += 100.0
            err += 5.0  # 5% errors = 5x burn of a 1% budget
            db.add("reqs", {}, total, "counter", t)
            db.add("errs", {}, err, "counter", t)
            t += 600.0
        db.flush_once(seal=True)
        db.stop()
        db2 = _mk(tmp_path)
        eng = SLOEngine(db2, specs=[spec], interval_s=9999.0)
        eng.evaluate_once(now=now)
        st = eng.status("api").to_dict()
        assert st["state"] in ("pending", "firing")
        pairs = st["pairs"]
        assert pairs[0]["fast_burn"] is None  # fast pair: no traffic
        assert pairs[1]["fast_burn"] == pytest.approx(5.0, rel=0.15)
        assert pairs[1]["slow_burn"] == pytest.approx(5.0, rel=0.15)
        db2.stop()

    def test_all_pairs_quiet_holds_state(self, tmp_path):
        spec = _burn_spec(extra_pairs=((1800.0, 7200.0, 1.0),))
        db = TSDB(capacity=64)
        eng = SLOEngine(db, specs=[spec], interval_s=9999.0)
        eng.evaluate_once(now=T0)
        st = eng.status("api").to_dict()
        assert st["state"] == "inactive"
        assert all(p["fast_burn"] is None for p in st["pairs"])


# ---------------------------------------------------------------------------
# Monitor + console wiring
# ---------------------------------------------------------------------------


class TestMonitorWiring:
    def test_pio_tsdb_dir_builds_durable(self, tmp_path, monkeypatch):
        import threading

        from predictionio_tpu.obs.monitor import Monitor
        from predictionio_tpu.obs.registry import MetricsRegistry

        monkeypatch.setenv("PIO_TSDB_DIR", str(tmp_path / "t"))
        monkeypatch.setenv("PIO_TSDB_FLUSH_S", "9999")
        monkeypatch.setenv("PIO_TSDB_COMPACT_S", "9999")
        monitor = Monitor()
        assert isinstance(monitor.tsdb, DurableTSDB)
        assert monitor.snapshot_path is None  # durable supersedes it
        token = monitor.attach("t", MetricsRegistry())
        names = {t.name for t in threading.enumerate()}
        assert "tsdb-wal" in names and "tsdb-compactor" in names
        payload = monitor.tsdb_payload({})
        assert "durable" in payload
        assert payload["durable"]["dir"] == str(tmp_path / "t")
        monitor.detach(token)
        names = {t.name for t in threading.enumerate()}
        assert "tsdb-wal" not in names
        assert "tsdb-compactor" not in names

    def test_console_summary_prints_durable(self, tmp_path, monkeypatch,
                                            capsys):
        from predictionio_tpu.obs import monitor as monitor_pkg
        from predictionio_tpu.obs.monitor import Monitor
        from predictionio_tpu.tools.console import cmd_tsdb

        monkeypatch.setenv("PIO_TSDB_DIR", str(tmp_path / "t"))
        monkeypatch.setenv("PIO_TSDB_FLUSH_S", "9999")
        m = Monitor()
        monkeypatch.setattr(monitor_pkg, "_monitor", m)
        m.tsdb.add("x", {}, 1.0, "gauge", T0)
        m.tsdb.flush_once(seal=True)

        class Args:
            url = None
            expr = None
            name = None
            labels = None
            window = None
            agg = None
            q = None
            last = None

        assert cmd_tsdb(Args()) == 0
        out = capsys.readouterr().out
        assert "durable tier at" in out
        assert "tier raw" in out
        m.tsdb.stop()


# ---------------------------------------------------------------------------
# WAL replay checkpoint cursor (ISSUE 19 satellite): a fat unsealed tail
# must not be re-parsed from byte 0 on every attach
# ---------------------------------------------------------------------------


class TestReplayCheckpoint:
    def test_replay_skips_pre_checkpoint_wal_bytes(self, tmp_path):
        """Fat-tail regression: after a checkpoint, reopening reads the
        active WAL segment from the cursor's byte offset — the thousands
        of pre-checkpoint lines are seeded from the snapshot, not
        re-parsed."""
        db = _mk(tmp_path)
        _walk(db, "fat", {"h": "a"}, T0, T0 + 30_000, 10.0, 1.0)  # 3001 pts
        db.flush_once(seal=False)
        cur = db.checkpoint_once()
        assert cur["off"] > 0
        last = _walk(db, "fat", {"h": "a"}, T0 + 30_010, T0 + 30_500,
                     10.0, 1.0, v0=3001.0)
        db.stop()

        reads = []
        real = DurableTSDB._read_wal_segment

        def spy(path, offset=0):
            reads.append((os.path.basename(path), offset))
            return real(path, offset)

        DurableTSDB._read_wal_segment = staticmethod(spy)
        try:
            db2 = _mk(tmp_path)
        finally:
            DurableTSDB._read_wal_segment = staticmethod(real)
        # every replay read of the checkpointed segment started at the
        # cursor offset — no read from byte 0
        seg = f"w-{cur['seq']:08d}.log"
        seg_reads = [off for name, off in reads if name == seg]
        assert seg_reads and all(off == cur["off"] for off in seg_reads)
        assert db2.ckpt_seeded_points > 0
        # and nothing was lost past the mark: the post-checkpoint walk
        # is all there
        pts = db2.matching("fat", {"h": "a"})[0].points
        assert pts[-1][1] == pytest.approx(last)
        stats = db2.durable_stats()
        assert stats["ckpt_seeded_points"] == db2.ckpt_seeded_points
        db2.stop()

    def test_checkpoint_replay_matches_full_replay(self, tmp_path):
        """Seeding from the snapshot + post-mark bytes must reconstruct
        exactly the rings a full WAL re-read builds."""
        import shutil

        db = _mk(tmp_path)
        for h in ("a", "b"):
            _walk(db, "m", {"h": h}, T0, T0 + 12_000, 10.0, 1.0)
        db.flush_once(seal=False)
        db.checkpoint_once()
        for h in ("a", "b"):
            _walk(db, "m", {"h": h}, T0 + 12_010, T0 + 12_300, 10.0, 1.0,
                  v0=1201.0)
        db.stop()
        shutil.copytree(str(tmp_path / "tsdb"), str(tmp_path / "full"))
        os.remove(str(tmp_path / "full" / "wal" / "ckpt.json"))

        with_ckpt = _mk(tmp_path)
        no_ckpt = DurableTSDB(str(tmp_path / "full"), capacity=720,
                              flush_interval_s=9999.0, seal_age_s=9999.0)
        assert with_ckpt.ckpt_seeded_points > 0
        assert no_ckpt.ckpt_seeded_points == 0
        for h in ("a", "b"):
            assert list(with_ckpt.matching("m", {"h": h})[0].points) == \
                list(no_ckpt.matching("m", {"h": h})[0].points)
        with_ckpt.stop()
        no_ckpt.stop()

    def test_periodic_checkpoint_rides_flush(self, tmp_path):
        db = _mk(tmp_path, ckpt_points=100)
        _walk(db, "c", {}, T0, T0 + 2_500, 10.0, 1.0)  # 251 points
        db.flush_once(seal=False)
        assert db.ckpt_written >= 1
        stats = db.durable_stats()
        assert stats["wal"]["ckpt_pending_points"] < 100
        assert stats["ckpt_written"] == db.ckpt_written
        db.stop()
