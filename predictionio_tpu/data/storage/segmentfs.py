"""segmentfs — columnar LSM-style event backend (ISSUE 13 tentpole).

The write path of an event store wants an append-only log; the training
read path wants struct-of-arrays columns it can hand to the device
loader without touching Python per row. segmentfs is both, behind the
EXISTING `EventStore` contract:

- **Ingest** appends to a per-(app, channel) fsync'd WAL — positional
  JSON rows under the resilience-WAL framing (one JSON value per line,
  fsync before ack, torn tails from a crash mid-append are skipped on
  reopen exactly like resilience/wal.py's `_read_records`) — and
  assigns the server-side insert revisions the online consumer tails
  by. One `insert_batch` is ONE row encode + one write + one fsync, and
  the accepted rows stay in memory as the unsealed tail in the same
  row-list form the WAL holds: no Event copies, no re-validation — this
  is where the 100k+ events/s comes from.
- A background **sealer** drains the unsealed tail into immutable
  column segments: the same struct-of-arrays layout as
  `data/store/columnar.py` (event_code / entity_idx / target_idx /
  time_ms / value columns) plus id/properties sidecars for full Event
  reads, per-segment **vocab deltas**, min/max revision, time range,
  and a bloom-filtered entity set in a `footer.json`. The build
  consumes the tail's row lists with vectorized interning and runs
  OUTSIDE the store lock (ingest keeps appending to a rotated WAL
  file), so sealing steals almost nothing from the ingest path.
  Segments are keyed by their revision range, so `find_since` is an
  indexed range read (binary search over segment footers, then a
  rev-column slice) and segment boundaries double as stream
  checkpoints — revisions are stable through seal and compaction, so a
  consumer cursor is exactly-once across both.
- **find_frame** is mmap + column concat + vectorized vocab remap: no
  per-row Python for sealed rows (the unsealed tail — bounded by the
  seal threshold — is the only row loop). The sealed portion is cached
  keyed by segment ids, so a retrain after more ingest folds only the
  tail. Scalar-numeric properties are extracted to float32 columns at
  seal time; `value_prop` reads become a column load.
- Background **compaction** merges small adjacent segments, dropping
  dead rows (deleted / overwritten) and rewriting the vocab deltas;
  revision values are preserved, so tail cursors stay valid.
- `data_signature` is O(1) metadata: (max revision, delete ops) — every
  mutation either assigns a new revision or records a delete.

Durability contract: an acked insert is in the fsync'd WAL (FSYNC=0
trades that for raw speed, like sqlite synchronous=OFF); sealing is an
atomic directory rename, and a crash between seal and WAL reclaim
dedupes by revision on reopen (WAL records at or below the last sealed
revision are skipped).

Overwrite semantics match the SQL backends' INSERT OR REPLACE: an
insert with an existing event id supersedes the old row (the old sealed
row is masked dead, the id's revision advances).

Layout under PATH::

    app_{appId}[_{channelId}]/
      wal-{seq:06d}.jsonl           # unsealed tail, [first_rev, [row,...]] per batch
      tombstones.json               # {"deleted": {id: rev}, "ops": N}
      meta.json                     # {"rev_floor": high-water revision}
      seg-{minrev:012d}-{maxrev:012d}/
        rev.npy event_code.npy etype_code.npy entity_idx.npy
        ttype_code.npy target_idx.npy time_ms.npy ctime_ms.npy
        val-{k}.npy                 # one float32 column per numeric prop
        ids.json rows.json          # sidecars: event ids; [props, tags, prId]
        footer.json                 # vocab deltas + min/max rev + bloom

Configure::

    PIO_STORAGE_SOURCES_<NAME>_TYPE=segmentfs
    PIO_STORAGE_SOURCES_<NAME>_PATH=/var/pio/segments
    # optional: SEAL_EVENTS (8192), SEAL_AGE_S (2.0), SEAL_INTERVAL_S
    # (0.25), COMPACT_SEGMENTS (8), COMPACT_MAX_ROWS (65536), FSYNC (1)

and point PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE at it (metadata/
models stay on a SQL/doc source — segmentfs stores events only, the
way the reference kept HBase for events and JDBC/ES for metadata).
"""

from __future__ import annotations

import bisect
import datetime as _dt
import hashlib
import json
import logging
import os
import shutil
import threading
import time
import zlib
from typing import Any, Iterator, Optional, Sequence

import numpy as np

from predictionio_tpu.analysis import tsan as _tsan
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import base
from predictionio_tpu.data.storage.base import EventQuery, StorageError

log = logging.getLogger(__name__)

_UTC = _dt.timezone.utc


def _ms(t: _dt.datetime) -> int:
    return int(t.timestamp() * 1000)


def _from_ms(ms: int) -> _dt.datetime:
    return _dt.datetime.fromtimestamp(ms / 1000.0, _UTC)


# max numeric properties columnized per segment — beyond this, value
# extraction for the overflow props falls back to the rows.json sidecar
_MAX_VALUE_PROPS = 16

# positional row layout shared by the WAL, the unsealed tail, and the
# seal build: one attribute walk per event at insert, reused everywhere
# (an Event re-materializes only on the read paths that need one)
# [0]=event_id [1]=event [2]=entity_type [3]=entity_id
# [4]=target_entity_type [5]=target_entity_id [6]=properties dict
# [7]=event_time_ms [8]=tags list|None [9]=pr_id [10]=creation_time_ms
_ROW_ID, _ROW_EVENT, _ROW_ETYPE, _ROW_EID = 0, 1, 2, 3
_ROW_TTYPE, _ROW_TID, _ROW_PROPS, _ROW_TIME = 4, 5, 6, 7
_ROW_TAGS, _ROW_PRID, _ROW_CTIME = 8, 9, 10

#: rows per vectorized materializer page (ISSUE 14): bounds the decoded
#: per-column working set on big unfiltered scans
_PAGE_ROWS = 2048


def _event_row(e: Event, eid: str) -> list:
    return [
        eid, e.event, e.entity_type, e.entity_id,
        e.target_entity_type, e.target_entity_id,
        e.properties.to_dict(), _ms(e.event_time),
        list(e.tags) if e.tags else None, e.pr_id,
        _ms(e.creation_time),
    ]


def _row_event(row: Sequence, rev: int) -> Event:
    """Row → Event WITHOUT re-running __init__/validation: every row
    was validated when its event was first inserted, and re-validating
    per materialized row made a 512-event `find_since` page ~2× slower
    than it needs to be."""
    e = object.__new__(Event)
    d = e.__dict__
    d["event"] = row[_ROW_EVENT]
    d["entity_type"] = row[_ROW_ETYPE]
    d["entity_id"] = row[_ROW_EID]
    d["target_entity_type"] = row[_ROW_TTYPE]
    d["target_entity_id"] = row[_ROW_TID]
    d["properties"] = DataMap(row[_ROW_PROPS] or {})
    d["event_time"] = _from_ms(row[_ROW_TIME])
    d["tags"] = tuple(row[_ROW_TAGS] or ())
    d["pr_id"] = row[_ROW_PRID]
    d["creation_time"] = _from_ms(row[_ROW_CTIME])
    d["event_id"] = row[_ROW_ID]
    d["revision"] = rev
    return e


def _gen_ids(n: int) -> list[str]:
    """`n` event ids in ONE entropy syscall (new_event_id() pays a
    posix.urandom round trip per id — a third of sqlite-era batch-insert
    time). Same 32-hex-char shape as uuid4().hex."""
    raw = os.urandom(16 * n).hex()
    return [raw[i << 5 : (i + 1) << 5] for i in range(n)]


# ---------------------------------------------------------------------------
# Bloom filter over a segment's entity-id set (footer metadata). Exactness
# is not required — the footer also carries the exact vocab — the bloom is
# the cheap first gate that skips a segment without building its id→idx
# dict (entity-scoped serving reads over many segments).
# ---------------------------------------------------------------------------


def _bloom_build(ids: Sequence[str], bits_per_key: int = 10) -> tuple[bytes, int]:
    n_bits = max(64, len(ids) * bits_per_key)
    arr = bytearray((n_bits + 7) // 8)
    for s in ids:
        for salt in (0, 0x9E3779B9, 0x85EBCA6B):
            h = zlib.crc32(s.encode(), salt) % n_bits
            arr[h >> 3] |= 1 << (h & 7)
    return bytes(arr), n_bits


def _bloom_maybe(bloom: bytes, n_bits: int, s: str) -> bool:
    for salt in (0, 0x9E3779B9, 0x85EBCA6B):
        h = zlib.crc32(s.encode(), salt) % n_bits
        if not (bloom[h >> 3] & (1 << (h & 7))):
            return False
    return True


# ---------------------------------------------------------------------------
# Sealed segment
# ---------------------------------------------------------------------------


class _Segment:
    """One immutable sealed segment: footer eagerly loaded, columns and
    sidecars lazily mmapped/parsed and cached on the instance."""

    def __init__(self, path: str):
        self.path = path
        with open(os.path.join(path, "footer.json")) as f:
            self.footer = json.load(f)
        self.min_rev: int = self.footer["min_rev"]
        self.max_rev: int = self.footer["max_rev"]
        self.n_rows: int = self.footer["n_rows"]
        self._bloom = bytes.fromhex(self.footer["entity_bloom"])
        self._bloom_bits: int = self.footer["bloom_bits"]
        # row indices masked dead by later overwrites/deletes (rebuilt
        # from the id scan on open; appended to by live mutations)
        self.dead: set[int] = set()
        self._cols: dict[str, np.ndarray] = {}
        self._ids: Optional[list[str]] = None
        self._ids_np: Optional[np.ndarray] = None
        self._rows: Optional[list] = None
        self._vocab_np: dict[str, np.ndarray] = {}

    def col(self, name: str) -> np.ndarray:
        a = self._cols.get(name)
        if a is None:
            a = np.load(os.path.join(self.path, f"{name}.npy"), mmap_mode="r")
            self._cols[name] = a
        return a

    def value_col(self, prop: str) -> Optional[np.ndarray]:
        """float32 column for a seal-extracted numeric property (NaN =
        absent on that row); None when the prop wasn't columnized."""
        idx = self.footer["value_props"].get(prop)
        if idx is None:
            return None
        return self.col(f"val-{idx}")

    def ids(self) -> list[str]:
        if self._ids is None:
            with open(os.path.join(self.path, "ids.json")) as f:
                self._ids = json.load(f)
        return self._ids

    def ids_np(self) -> np.ndarray:
        if self._ids_np is None:
            self._ids_np = np.asarray(self.ids())
        return self._ids_np

    def vocab_np(self, key: str) -> np.ndarray:
        """Footer vocab as a numpy string array (vectorized row
        materialization: vocab_np[idx_col])."""
        a = self._vocab_np.get(key)
        if a is None:
            vals = self.footer[key]
            a = np.asarray(vals) if vals else np.asarray([""], dtype=str)
            self._vocab_np[key] = a
        return a

    def sidecar_rows(self) -> list:
        """[properties_dict, tags_list, pr_id] per row (the full-Event
        sidecar; only the generic read path touches it)."""
        if self._rows is None:
            with open(os.path.join(self.path, "rows.json")) as f:
                self._rows = json.load(f)
        return self._rows

    def row_of_rev(self, rev: int) -> Optional[int]:
        """Row index holding revision `rev` (None if absent). Revisions
        are sorted ascending within a segment (contiguous pre-compaction,
        gappy after), so this is a searchsorted."""
        col = self.col("rev")
        i = int(np.searchsorted(col, rev))
        if i < len(col) and int(col[i]) == rev:
            return i
        return None

    def maybe_has_entity(self, entity_id: str) -> bool:
        return _bloom_maybe(self._bloom, self._bloom_bits, entity_id)

    def has_target(self, target_id: str) -> bool:
        """Exact posting check: the footer target vocab IS the posting
        list existence test (per-item fold-in index, ISSUE 13 satellite)."""
        return target_id in self.footer["target_ids"]

    def row(self, i: int) -> list:
        """Row `i` in the shared positional layout (seal/compact feed)."""
        props, tags, pr_id = self.sidecar_rows()[i]
        ttype_i = int(self.col("ttype_code")[i])
        tgt_i = int(self.col("target_idx")[i])
        return [
            self.ids()[i],
            self.footer["event_names"][int(self.col("event_code")[i])],
            self.footer["entity_types"][int(self.col("etype_code")[i])],
            self.footer["entity_ids"][int(self.col("entity_idx")[i])],
            self.footer["target_types"][ttype_i] if ttype_i >= 0 else None,
            self.footer["target_ids"][tgt_i] if tgt_i >= 0 else None,
            props,
            int(self.col("time_ms")[i]),
            tags or None,
            pr_id,
            int(self.col("ctime_ms")[i]),
        ]

    def event(self, i: int) -> Event:
        """Materialize row `i` as a full Event (generic read path)."""
        return _row_event(self.row(i), int(self.col("rev")[i]))

    def events_page(self, rows: np.ndarray) -> list[Event]:
        """Vectorized page materializer (ISSUE 14 satellite, carried
        data-plane follow-up): decode every needed column for a whole
        row page with ONE numpy fancy-index per column — the generic
        `find`/`find_since` scans used to pay 7 per-row mmap column
        reads plus footer-list indexing per Event. The Events
        themselves still build per row (they are python objects), but
        off already-decoded numpy arrays."""
        rows = np.asarray(rows, np.int64)
        if not len(rows):
            return []
        revs = np.asarray(self.col("rev"))[rows]
        names = self.vocab_np("event_names")[
            np.asarray(self.col("event_code"))[rows]
        ]
        etypes = self.vocab_np("entity_types")[
            np.asarray(self.col("etype_code"))[rows]
        ]
        eids = self.vocab_np("entity_ids")[
            np.asarray(self.col("entity_idx"))[rows]
        ]
        ttc = np.asarray(self.col("ttype_code"))[rows]
        tic = np.asarray(self.col("target_idx"))[rows]
        ttypes = self.vocab_np("target_types")[np.maximum(ttc, 0)]
        tids = self.vocab_np("target_ids")[np.maximum(tic, 0)]
        times = np.asarray(self.col("time_ms"))[rows]
        ctimes = np.asarray(self.col("ctime_ms"))[rows]
        ids = self.ids_np()[rows]
        sidecar = self.sidecar_rows()
        out: list[Event] = []
        for j, i in enumerate(rows):
            props, tags, pr_id = sidecar[i]
            e = object.__new__(Event)
            d = e.__dict__
            d["event"] = str(names[j])
            d["entity_type"] = str(etypes[j])
            d["entity_id"] = str(eids[j])
            d["target_entity_type"] = (
                str(ttypes[j]) if ttc[j] >= 0 else None
            )
            d["target_entity_id"] = (
                str(tids[j]) if tic[j] >= 0 else None
            )
            d["properties"] = DataMap(props or {})
            d["event_time"] = _from_ms(int(times[j]))
            d["tags"] = tuple(tags or ())
            d["pr_id"] = pr_id
            d["creation_time"] = _from_ms(int(ctimes[j]))
            d["event_id"] = str(ids[j])
            d["revision"] = int(revs[j])
            out.append(e)
        return out


def _rank_first_seen(sel: np.ndarray) -> tuple[list[str], np.ndarray]:
    """Vectorized first-seen intern core (BiMap.string_int semantics):
    (vocab list in first-seen order, int32 codes for `sel`). Shared by
    the seal-time column build and the frame-assembly vocab — ONE
    implementation, so the two can never diverge and break the
    bit-identical find_frame parity."""
    uniq, first, inv = np.unique(sel, return_index=True, return_inverse=True)
    order = np.argsort(first, kind="stable")
    rank = np.empty(len(uniq), np.int32)
    rank[order] = np.arange(len(uniq), dtype=np.int32)
    return [str(uniq[j]) for j in order], rank[inv].astype(np.int32)


def _first_seen(values: Sequence) -> tuple[list, np.ndarray]:
    """Intern a possibly-None value column: (vocab list in first-seen
    order, int32 codes; None values code -1). The np.unique path beats
    a per-row dict loop ~5× at seal scale."""
    arr = np.asarray(
        ["" if v is None else v for v in values], dtype=str
    )
    valid = np.asarray([v is not None for v in values], dtype=bool)
    sel = arr[valid]
    if not len(sel):
        return [], np.full(len(values), -1, np.int32)
    vocab, codes_sel = _rank_first_seen(sel)
    codes = np.full(len(values), -1, np.int32)
    codes[valid] = codes_sel
    return vocab, codes


def segment_content_hash(seg_dir: str) -> str:
    """Content address of a segment directory: sha256 over every data
    file's (name, sha256(bytes)), sorted by name, footer.json excluded
    (it HOLDS the hash). Replication verifies a shipped segment against
    this before publishing; segments sealed before the field existed
    hash identically because the computation never reads the footer."""
    acc = hashlib.sha256()
    for fname in sorted(os.listdir(seg_dir)):
        if fname == "footer.json" or fname.startswith("."):
            continue
        h = hashlib.sha256()
        with open(os.path.join(seg_dir, fname), "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        acc.update(fname.encode())
        acc.update(h.digest())
    return acc.hexdigest()


def _write_segment(
    ns_dir: str, rows: Sequence[Sequence], revs: Sequence[int]
) -> str:
    """Build one immutable segment from revision-ordered rows and
    publish it atomically (tmp dir + rename). Returns the segment path."""
    assert rows
    min_rev, max_rev = int(revs[0]), int(revs[-1])
    name = f"seg-{min_rev:012d}-{max_rev:012d}"
    tmp = os.path.join(ns_dir, f"tmp-{name}-{os.getpid()}")
    os.makedirs(tmp, exist_ok=True)

    (
        ids, names, etypes, eids, ttypes, tids, props, times, tags,
        prids, ctimes,
    ) = zip(*rows)

    event_names, event_code = _first_seen(names)
    entity_types, etype_code = _first_seen(etypes)
    entity_ids, entity_idx = _first_seen(eids)
    target_types, ttype_code = _first_seen(ttypes)
    target_ids, target_idx = _first_seen(tids)

    cols: dict[str, np.ndarray] = {
        "rev": np.asarray(revs, np.int64),
        "event_code": event_code,
        "etype_code": etype_code,
        "entity_idx": entity_idx,
        "ttype_code": ttype_code,
        "target_idx": target_idx,
        "time_ms": np.asarray(times, np.int64),
        "ctime_ms": np.asarray(ctimes, np.int64),
    }

    # numeric-property extraction: every top-level property that floats
    # cleanly on every row where present becomes a float32 column (NaN =
    # absent), so find_frame(value_prop=...) is a column read
    candidates: dict[str, int] = {}
    for p in props:
        for k in p:
            candidates[k] = candidates.get(k, 0) + 1
    value_props: dict[str, int] = {}
    for prop, _n in sorted(candidates.items(), key=lambda kv: -kv[1]):
        if len(value_props) >= _MAX_VALUE_PROPS:
            break
        col = np.full(len(rows), np.nan, np.float32)
        ok = True
        for i, p in enumerate(props):
            v = p.get(prop)
            if v is None:
                continue
            # same acceptance as DataMap's float cast: int/float, not bool
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                col[i] = v
            else:
                ok = False
                break
        if ok:
            idx = len(value_props)
            value_props[prop] = idx
            cols[f"val-{idx}"] = col

    for cname, arr in cols.items():
        np.save(os.path.join(tmp, f"{cname}.npy"), arr)
    with open(os.path.join(tmp, "ids.json"), "w") as f:
        json.dump(list(ids), f)
    with open(os.path.join(tmp, "rows.json"), "w") as f:
        json.dump(
            [[p, tg or [], pr] for p, tg, pr in zip(props, tags, prids)],
            f, default=str,
        )
    bloom, n_bits = _bloom_build(entity_ids)
    times_arr = cols["time_ms"]
    content_hash = segment_content_hash(tmp)
    with open(os.path.join(tmp, "footer.json"), "w") as f:
        json.dump(
            {
                "min_rev": min_rev,
                "max_rev": max_rev,
                "content_hash": content_hash,
                "n_rows": len(rows),
                "event_names": event_names,
                "entity_types": entity_types,
                "entity_ids": entity_ids,
                "target_types": target_types,
                "target_ids": target_ids,
                "value_props": value_props,
                "time_min_ms": int(times_arr.min()),
                "time_max_ms": int(times_arr.max()),
                "entity_bloom": bloom.hex(),
                "bloom_bits": n_bits,
            },
            f,
        )
    final = os.path.join(ns_dir, name)
    os.rename(tmp, final)
    return final


# ---------------------------------------------------------------------------
# Per-namespace state
# ---------------------------------------------------------------------------


class _Namespace:
    """Mutable state of one (app, channel): the unsealed tail (row
    lists; tail[i] holds revision tail_base + i, None = superseded),
    the sealed segment list, id → latest revision, tombstones. All
    access happens under the owning store's lock; the seal/compact
    builds snapshot under it and publish under it."""

    def __init__(self, path: str, fsync: bool):
        self.path = path
        self.fsync = fsync
        os.makedirs(path, exist_ok=True)
        self.segments: list[_Segment] = []
        self.tail: list[Optional[list]] = []
        self.tail_base = 1  # revision of tail[0]
        self.tail_by_id: dict[str, int] = {}  # id → tail index
        self.id_rev: dict[str, int] = {}  # live id → latest revision
        self.tombstones: dict[str, int] = {}  # deleted id → rev at delete
        self.delete_ops = 0
        self.next_rev = 1
        self.tail_since = 0.0  # monotonic stamp of the oldest tail event
        # maintenance guards: one seal / one compaction in flight per
        # namespace (the heavy builds run OUTSIDE the store lock so
        # ingest never stalls behind them)
        self.sealing = False
        self.compacting = False
        self.removed = False
        self._meta_path = os.path.join(path, "meta.json")
        self._wal_seq = 0
        self._wal_file = None
        self._recover()

    # -- open / crash recovery --------------------------------------------
    def _recover(self) -> None:
        # leftover tmp dirs are un-published seals from a crash: the WAL
        # still has their events, so they are garbage
        for n in os.listdir(self.path):
            if n.startswith("tmp-"):
                shutil.rmtree(os.path.join(self.path, n), ignore_errors=True)
        segs = sorted(
            n for n in os.listdir(self.path) if n.startswith("seg-")
        )
        self.segments = [
            _Segment(os.path.join(self.path, n)) for n in segs
        ]
        self.segments.sort(key=lambda s: s.min_rev)
        tomb_path = os.path.join(self.path, "tombstones.json")
        if os.path.exists(tomb_path):
            with open(tomb_path) as f:
                d = json.load(f)
            self.tombstones = {k: int(v) for k, v in d["deleted"].items()}
            self.delete_ops = int(d["ops"])
        # revision watermark: seal reclaims WAL files, and a tail whose
        # top rows were all deleted would otherwise lose the high-water
        # mark across restart — a restarted store must CONTINUE the
        # sequence, never reuse it (same contract as sqlite's
        # pio_insert_revisions seed)
        rev_floor = 0
        if os.path.exists(self._meta_path):
            with open(self._meta_path) as f:
                rev_floor = int(json.load(f).get("rev_floor", 0))
        # rebuild id → latest revision; later occurrences mask earlier
        # rows dead (overwrite), tombstones mask their id's rows dead
        where: dict[str, tuple[int, int]] = {}  # id → (seg idx, row)
        max_rev = 0
        for sx, seg in enumerate(self.segments):
            max_rev = max(max_rev, seg.max_rev)
            revs = seg.col("rev")
            for i, eid in enumerate(seg.ids()):
                prev = where.get(eid)
                if prev is not None:
                    self.segments[prev[0]].dead.add(prev[1])
                where[eid] = (sx, i)
                self.id_rev[eid] = int(revs[i])
        # WAL replay: records at or below the last sealed revision were
        # sealed before the crash reclaimed their WAL file — skip them
        # (the seal-then-reclaim crash window, exactly-once)
        from predictionio_tpu.resilience.wal import EventWAL

        self.tail_base = max_rev + 1
        for name in self._wal_files():
            self._wal_seq = max(
                self._wal_seq, int(name.split("-")[1].split(".")[0])
            )
            for rec in EventWAL._read_records(
                os.path.join(self.path, name)
            ):
                first = int(rec[0])
                for k, row in enumerate(rec[1]):
                    rev = first + k
                    if rev <= max_rev:
                        continue
                    # pad skipped-prefix holes so tail index ↔ revision
                    # stays affine (tail_base + i)
                    while self.tail_base + len(self.tail) < rev:
                        self.tail.append(None)
                    self._tail_append(row, rev, where)
                    max_rev = max(max_rev, rev)
        self.next_rev = max(max_rev, rev_floor) + 1
        for eid, rev in list(self.tombstones.items()):
            live = self.id_rev.get(eid)
            if live is None:
                del self.tombstones[eid]
            elif live <= rev:
                self._mask_dead(eid, where)
            else:
                del self.tombstones[eid]  # re-inserted after the delete
        if self.tail:
            self.tail_since = time.monotonic()

    def _tail_append(
        self, row: list, rev: int, where: Optional[dict] = None
    ) -> None:
        eid = row[_ROW_ID]
        prev_tail = self.tail_by_id.get(eid)
        if prev_tail is not None:
            self.tail[prev_tail] = None
        elif eid in self.id_rev:
            self._mask_sealed_dead(eid, where)
        self.tail_by_id[eid] = len(self.tail)
        self.tail.append(row)
        self.id_rev[eid] = rev

    def _mask_sealed_dead(
        self, eid: str, where: Optional[dict] = None
    ) -> None:
        rev = self.id_rev.get(eid)
        if rev is None:
            return
        if where is not None:
            loc = where.get(eid)
            if loc is not None:
                self.segments[loc[0]].dead.add(loc[1])
                return
        seg = self.segment_for_rev(rev)
        if seg is not None:
            row = seg.row_of_rev(rev)
            if row is not None:
                seg.dead.add(row)

    def _mask_dead(self, eid: str, where: Optional[dict] = None) -> None:
        """Tombstone/overwrite masking of id's current row + id_rev drop."""
        ti = self.tail_by_id.pop(eid, None)
        if ti is not None:
            self.tail[ti] = None
        else:
            self._mask_sealed_dead(eid, where)
        self.id_rev.pop(eid, None)

    # -- WAL ---------------------------------------------------------------
    def _wal_files(self) -> list[str]:
        """WAL file names, oldest first (fixed-width seq in the name)."""
        try:
            return sorted(
                n for n in os.listdir(self.path)
                if n.startswith("wal-") and n.endswith(".jsonl")
            )
        except FileNotFoundError:
            return []

    def wal_append(self, line: str) -> None:
        if self._wal_file is None:
            self._wal_seq += 1
            self._wal_file = open(
                os.path.join(
                    self.path, f"wal-{self._wal_seq:06d}.jsonl"
                ),
                "a",
            )
        self._wal_file.write(line)
        self._wal_file.flush()
        if self.fsync:
            # blocking point (ISSUE 15 satellite): ingest holds the
            # store lock across this fsync BY DESIGN (fsync-before-ack
            # + revision assignment are one critical section; the store
            # lock is declared allowed) — any OTHER lock held into
            # insert_batch is a finding
            _tsan.note_blocking("wal.fsync")
            os.fsync(self._wal_file.fileno())

    def wal_rotate(self) -> list[str]:
        """Close the current WAL file so later appends open a fresh one;
        returns the existing file paths — they hold exactly the records
        assigned so far and are reclaimable once those records seal."""
        if self._wal_file is not None:
            self._wal_file.close()
            self._wal_file = None
        return [os.path.join(self.path, n) for n in self._wal_files()]

    def persist_rev_floor(self) -> None:
        """Durably record the high-water revision BEFORE the WAL files
        are reclaimed by a seal (fsync'd tmp + atomic replace)."""
        tmp = self._meta_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"rev_floor": self.next_rev - 1}, f)
            f.flush()
            if self.fsync:
                os.fsync(f.fileno())
        os.replace(tmp, self._meta_path)

    def persist_tombstones(self) -> None:
        tmp = os.path.join(self.path, "tombstones.json.tmp")
        with open(tmp, "w") as f:
            json.dump(
                {"deleted": self.tombstones, "ops": self.delete_ops}, f
            )
            f.flush()
            if self.fsync:
                os.fsync(f.fileno())
        os.replace(tmp, os.path.join(self.path, "tombstones.json"))

    # -- lookups -----------------------------------------------------------
    def segment_for_rev(self, rev: int) -> Optional[_Segment]:
        keys = [s.min_rev for s in self.segments]
        i = bisect.bisect_right(keys, rev) - 1
        if 0 <= i < len(self.segments) and self.segments[i].max_rev >= rev:
            return self.segments[i]
        return None

    def live_tail(self) -> list[tuple[int, list]]:
        """(revision, row) for every live unsealed row, revision order."""
        return [
            (self.tail_base + i, row)
            for i, row in enumerate(self.tail)
            if row is not None
        ]

    def close(self) -> None:
        if self._wal_file is not None:
            self._wal_file.close()
            self._wal_file = None


# ---------------------------------------------------------------------------
# The store
# ---------------------------------------------------------------------------


class SegmentFSEventStore(base.EventStore):
    """Columnar LSM event store. See the module docstring for layout and
    contracts."""

    def __init__(self, config: Optional[dict] = None):
        config = config or {}
        path = config.get("PATH")
        if not path:
            raise StorageError("segmentfs requires a PATH setting")
        self.base = path
        os.makedirs(self.base, exist_ok=True)
        self.fsync = str(config.get("FSYNC", "1")).strip() not in (
            "0", "false", "no",
        )
        self.seal_events = int(config.get("SEAL_EVENTS", 8192))
        self.seal_age_s = float(config.get("SEAL_AGE_S", 2.0))
        self.seal_interval_s = float(config.get("SEAL_INTERVAL_S", 0.25))
        self.compact_segments = int(config.get("COMPACT_SEGMENTS", 8))
        self.compact_max_rows = int(config.get("COMPACT_MAX_ROWS", 65536))
        self._lock = threading.RLock()
        _tsan.allow_blocking_lock(self._lock)  # holds the WAL fsync by design
        # cross-process writer guard (ISSUE 15 satellite, carried
        # PR-13 item (c)): segmentfs assumes ONE writer process per
        # PATH — a second process interleaving WAL appends and seals
        # would corrupt the revision sequence silently. An exclusive
        # POSIX record lock on <PATH>/.writer.lock makes the second
        # process fail FAST with a clear error instead. lockf locks
        # are per-process, so crash-recovery tests (and a same-process
        # reopen after an unclean "crash") still work — the guard
        # targets exactly the cross-process double-writer.
        self._writer_lock_file = self._acquire_writer_lock()
        self._ns: dict[tuple[int, Optional[int]], _Namespace] = {}
        # sealed-rows frame cache: query key → (validity token, arrays)
        self._frame_cache: dict[tuple, tuple[tuple, dict]] = {}
        self.frame_cache_stats = {"hits": 0, "misses": 0}
        self.segments_scanned = 0  # target-posting prune introspection
        self._stop = threading.Event()
        self._sealer: Optional[threading.Thread] = None
        # replication seam: when set (SegmentShipper with MIN_ACKS>0),
        # called under the store lock after the WAL append + state
        # update with (app_id, channel_id, first_rev, rows, head); a
        # raise propagates to the caller so "acked ⇒ replicated"
        self._commit_hook = None

    # -- cross-process writer guard ---------------------------------------
    def _acquire_writer_lock(self):
        """Exclusive fcntl.lockf on <PATH>/.writer.lock. Held for the
        store's lifetime (released in close(), or by the OS when the
        process dies — which is what lets a restart after kill -9
        reopen immediately). A second PROCESS gets StorageError with
        the holder's pid instead of silent WAL/segment corruption."""
        try:
            import fcntl
        except ImportError:  # non-POSIX: no guard, preserve behavior
            return None
        lock_path = os.path.join(self.base, ".writer.lock")
        f = open(lock_path, "a+")
        try:
            fcntl.lockf(f, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            try:
                f.seek(0)
                holder = f.read().strip() or "unknown"
            except OSError:
                holder = "unknown"
            f.close()
            raise StorageError(
                f"segmentfs store at {self.base!r} is already open for "
                f"writing by another process (pid {holder}); segmentfs "
                "allows ONE writer process per PATH — route writes "
                "through the storage daemon, or close the other process"
            )
        f.truncate(0)
        f.write(f"{os.getpid()}\n")
        f.flush()
        return f

    def _release_writer_lock(self) -> None:
        f = self._writer_lock_file
        if f is None:
            return
        self._writer_lock_file = None
        try:
            import fcntl

            fcntl.lockf(f, fcntl.LOCK_UN)
        except Exception:
            pass
        try:
            f.close()
        except OSError:
            pass

    # -- sealer thread -----------------------------------------------------
    def _ensure_sealer(self) -> None:
        if self._sealer is not None and self._sealer.is_alive():
            return
        with self._lock:
            if self._sealer is not None and self._sealer.is_alive():
                return
            self._stop.clear()
            self._sealer = threading.Thread(
                target=self._sealer_loop, name="segmentfs-sealer",
                daemon=True,
            )
            self._sealer.start()

    def _sealer_loop(self) -> None:
        while not self._stop.wait(self.seal_interval_s):
            try:
                self.maintain()
            except Exception:
                log.exception("segmentfs sealer pass failed; will retry")

    def maintain(self) -> None:
        """One seal+compact pass over every namespace (public so tests
        and `pio` tools drive it without the thread)."""
        with self._lock:
            keys = list(self._ns)
        now = time.monotonic()
        for key in keys:
            with self._lock:
                ns = self._ns.get(key)
                if ns is None:
                    continue
                n_tail = len(ns.tail_by_id)
                due = n_tail >= self.seal_events or (
                    n_tail > 0 and now - ns.tail_since >= self.seal_age_s
                )
                do_compact = len(ns.segments) > self.compact_segments
            # seal/compact builds run OUTSIDE the lock (they re-check
            # their own guards) so ingest never stalls behind them
            if due:
                self._seal_ns(ns)
            if do_compact:
                self._compact_ns(ns)

    def close(self) -> None:
        self._stop.set()
        t = self._sealer
        if t is not None:
            t.join(timeout=10)
            self._sealer = None
        # final seal so a clean shutdown leaves no WAL to replay
        with self._lock:
            namespaces = list(self._ns.values())
        for ns in namespaces:
            try:
                self._seal_ns(ns)
            except Exception:
                log.exception("segmentfs close-time seal failed")
            ns.close()
        self._release_writer_lock()

    # -- namespace plumbing ------------------------------------------------
    def _dir(self, app_id: int, channel_id: Optional[int]) -> str:
        name = f"app_{app_id}" + (f"_{channel_id}" if channel_id else "")
        return os.path.join(self.base, name)

    def _namespace(self, app_id: int, channel_id: Optional[int]) -> _Namespace:
        key = (app_id, channel_id)
        ns = self._ns.get(key)
        if ns is None:
            ns = _Namespace(self._dir(app_id, channel_id), self.fsync)
            self._ns[key] = ns
        return ns

    def init_app(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        with self._lock:
            self._namespace(app_id, channel_id)
        return True

    def remove_app(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        with self._lock:
            ns = self._ns.pop((app_id, channel_id), None)
            if ns is not None:
                ns.removed = True
                ns.close()
            d = self._dir(app_id, channel_id)
            if os.path.isdir(d):
                shutil.rmtree(d)
            self._invalidate_frames(app_id, channel_id)
        return True

    def _invalidate_frames(self, app_id, channel_id) -> None:
        for k in [
            k for k in self._frame_cache if k[0] == (app_id, channel_id)
        ]:
            del self._frame_cache[k]

    # -- writes ------------------------------------------------------------
    def insert(
        self, event: Event, app_id: int, channel_id: Optional[int] = None
    ) -> str:
        return self.insert_batch([event], app_id, channel_id)[0]

    def insert_batch(
        self, events: Sequence[Event], app_id: int,
        channel_id: Optional[int] = None,
    ) -> list[str]:
        if not events:
            return []
        self._ensure_sealer()
        fresh = iter(_gen_ids(sum(1 for e in events if e.event_id is None)))
        with self._lock:
            ns = self._namespace(app_id, channel_id)
            first = ns.next_rev
            rows = [
                _event_row(e, e.event_id or next(fresh)) for e in events
            ]
            # ONE encode + one write + one fsync for the whole batch —
            # the ack is a durability promise, paid once per call. A
            # torn batch line is by definition an UNACKED batch, so the
            # batch-granular record keeps the torn-tail recovery exact.
            # WAL FIRST, state second: if the append raises (disk
            # full), no in-memory state has changed — otherwise the
            # sealer would persist rows the caller was told FAILED, and
            # a client retry would duplicate every event in the batch.
            try:
                ns.wal_append(
                    json.dumps(
                        [first, rows], separators=(",", ":"), default=str
                    ) + "\n"
                )
            except BaseException:
                # burn the claimed revisions: the failed record may
                # still be complete on disk (fsync raised after the
                # write), and a later batch reusing its revisions would
                # make recovery drop the ACKED batch as a duplicate.
                # The None slots keep the tail's index ↔ revision
                # mapping affine (tail_base + i).
                ns.next_rev += len(events)
                ns.tail.extend([None] * len(events))
                raise
            was_empty = not ns.tail_by_id
            ns.next_rev += len(events)
            for i, row in enumerate(rows):
                ns._tail_append(row, first + i)
            if was_empty:
                ns.tail_since = time.monotonic()
            hook = self._commit_hook
            if hook is not None:
                # sync replication: still under the store lock so frames
                # reach followers in revision order. On a raise the rows
                # stay durable LOCALLY (WAL is already fsync'd) and the
                # background ship pass re-sends them — same at-least-once
                # class as a batch whose fsync raised after the write —
                # but the caller sees the failure, so an ACK always
                # means the frame reached MIN_ACKS followers.
                hook(app_id, channel_id, first, rows, ns.next_rev - 1)
            return [row[_ROW_ID] for row in rows]

    def delete(
        self, event_id: str, app_id: int, channel_id: Optional[int] = None
    ) -> bool:
        return self.delete_batch([event_id], app_id, channel_id) == 1

    def delete_batch(
        self,
        event_ids: Sequence[str],
        app_id: int,
        channel_id: Optional[int] = None,
    ) -> int:
        with self._lock:
            ns = self._namespace(app_id, channel_id)
            hits = 0
            for eid in dict.fromkeys(event_ids):
                rev = ns.id_rev.get(eid)
                if rev is None:
                    continue
                ns.tombstones[eid] = rev
                ns._mask_dead(eid)
                ns.delete_ops += 1
                hits += 1
            if hits:
                ns.persist_tombstones()
        return hits

    # -- reads: generic ----------------------------------------------------
    def get(
        self, event_id: str, app_id: int, channel_id: Optional[int] = None
    ) -> Optional[Event]:
        with self._lock:
            ns = self._namespace(app_id, channel_id)
            rev = ns.id_rev.get(event_id)
            if rev is None:
                return None
            ti = ns.tail_by_id.get(event_id)
            if ti is not None:
                return _row_event(ns.tail[ti], rev)
            seg = ns.segment_for_rev(rev)
            if seg is None:
                return None
            row = seg.row_of_rev(rev)
            return seg.event(row) if row is not None else None

    def _iter_live(
        self, ns: _Namespace, query: EventQuery
    ) -> Iterator[Event]:
        """Live events of the namespace, segment-pruned where the query
        allows: entity-scoped reads gate on the bloom + exact vocab,
        target-scoped reads on the footer's target posting set, time
        ranges on the footer's min/max stamps. Caller holds the lock."""
        for seg in ns.segments:
            if (
                query.entity_id is not None
                and not (
                    seg.maybe_has_entity(query.entity_id)
                    and query.entity_id in seg.footer["entity_ids"]
                )
            ):
                continue
            if (
                query.target_entity_id is not None
                and not seg.has_target(query.target_entity_id)
            ):
                continue
            if (
                query.start_time is not None
                and seg.footer["time_max_ms"] < _ms(query.start_time)
            ):
                continue
            if (
                query.until_time is not None
                and seg.footer["time_min_ms"] >= _ms(query.until_time)
            ):
                continue
            self.segments_scanned += 1
            dead = seg.dead
            # posting-list row selection (ISSUE 13 satellite: the item
            # fold-in history read): a point filter on target or entity
            # selects its rows by code match — one vectorized compare,
            # and only the hits materialize as Events
            if query.target_entity_id is not None:
                code = seg.footer["target_ids"].index(query.target_entity_id)
                rows = np.nonzero(seg.col("target_idx") == code)[0]
            elif query.entity_id is not None:
                code = seg.footer["entity_ids"].index(query.entity_id)
                rows = np.nonzero(seg.col("entity_idx") == code)[0]
            else:
                rows = np.arange(seg.n_rows)
            if dead:
                rows = rows[~np.isin(rows, np.fromiter(dead, np.int64))]
            # vectorized page materializer (ISSUE 14): whole pages
            # decode per-column instead of 7 mmap reads per row; pages
            # stay bounded so a huge segment never materializes at once
            for lo in range(0, len(rows), _PAGE_ROWS):
                yield from seg.events_page(rows[lo : lo + _PAGE_ROWS])
        for rev, row in ns.live_tail():
            yield _row_event(row, rev)

    def find(self, query: EventQuery) -> Iterator[Event]:
        with self._lock:
            ns = self._namespace(query.app_id, query.channel_id)
            matches = [
                e for e in self._iter_live(ns, query) if query.matches(e)
            ]
        matches.sort(
            key=lambda e: (e.event_time, e.event_id or ""),
            reverse=query.reversed,
        )
        if query.limit is not None and query.limit >= 0:
            matches = matches[: query.limit]
        return iter(matches)

    # -- revisions (the online consumer's tail) ----------------------------
    def latest_revision(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> int:
        with self._lock:
            return self._namespace(app_id, channel_id).next_rev - 1

    def find_since(
        self,
        app_id: int,
        after_revision: int,
        channel_id: Optional[int] = None,
        limit: Optional[int] = None,
        shard: Optional[tuple[int, int]] = None,
    ) -> list[Event]:
        """Indexed tail read: segments are keyed by revision range, so
        the cursor binary-searches to its segment and reads forward —
        O(page + log segments), never a namespace scan."""
        with self._lock:
            ns = self._namespace(app_id, channel_id)
            out: list[Event] = []

            def full() -> bool:
                return limit is not None and 0 <= limit <= len(out)

            keys = [s.max_rev for s in ns.segments]
            sx = bisect.bisect_left(keys, after_revision + 1)
            for seg in ns.segments[sx:]:
                if full():
                    break
                revs = seg.col("rev")
                start = int(np.searchsorted(revs, after_revision + 1))
                rows = np.arange(start, seg.n_rows)
                if seg.dead:
                    rows = rows[
                        ~np.isin(rows, np.fromiter(seg.dead, np.int64))
                    ]
                # paged vectorized materialization (ISSUE 14): decode
                # whole pages per column; pages shrink toward a small
                # `limit` (scaled by the shard fan-out, which passes
                # ~1/n of rows) so a tail read never decodes far past
                # what it returns
                lo = 0
                while lo < len(rows) and not full():
                    chunk = _PAGE_ROWS
                    if limit is not None and limit >= 0:
                        need = (limit - len(out)) * (
                            shard[1] if shard is not None else 1
                        )
                        chunk = max(64, min(_PAGE_ROWS, need))
                    for e in seg.events_page(rows[lo : lo + chunk]):
                        if full():
                            break
                        if shard is not None and base.shard_of(
                            e.entity_id, shard[1]
                        ) != shard[0]:
                            continue
                        out.append(e)
                    lo += chunk
            for rev, row in ns.live_tail():
                if full():
                    break
                if rev <= after_revision:
                    continue
                if shard is not None and base.shard_of(
                    row[_ROW_EID], shard[1]
                ) != shard[0]:
                    continue
                out.append(_row_event(row, rev))
        return out

    def data_signature(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> str:
        # O(1) footer metadata: every mutation either assigns a new
        # revision (insert/overwrite) or bumps the delete-op counter
        with self._lock:
            ns = self._namespace(app_id, channel_id)
            return f"{ns.next_rev - 1}:{ns.delete_ops}"

    # -- seal / compact ----------------------------------------------------
    def seal(self, app_id: int, channel_id: Optional[int] = None) -> int:
        """Synchronously seal the namespace's tail; returns rows sealed
        (public: tests, `pio export`-style tools)."""
        with self._lock:
            ns = self._namespace(app_id, channel_id)
        return self._seal_ns(ns)

    def _seal_ns(self, ns: _Namespace) -> int:
        """Seal the tail snapshot into one immutable segment. The
        segment BUILD runs outside the store lock — ingest keeps
        appending to a fresh WAL file while the columns encode — and the
        publish step swaps atomically, marking any row that was deleted
        or overwritten mid-build dead in the new segment."""
        with self._lock:
            if ns.sealing or ns.removed or not ns.tail:
                return 0
            ns.sealing = True
            live = ns.live_tail()
            cut = len(ns.tail)
            old_wals = ns.wal_rotate()
        path: Optional[str] = None
        try:
            if live:
                path = _write_segment(
                    ns.path,
                    [row for _rev, row in live],
                    [rev for rev, _row in live],
                )
        except BaseException:
            # build failed: the tail and its WAL files are untouched —
            # publishing anything here would reclaim the WAL without a
            # segment and lose acked events; the next pass retries
            with self._lock:
                ns.sealing = False
            raise
        else:
            with self._lock:
                if ns.removed:
                    if path is not None:
                        shutil.rmtree(path, ignore_errors=True)
                    ns.sealing = False
                    return 0
                if path is not None:
                    seg = _Segment(path)
                    # rows mutated while the segment was building:
                    # their id's live revision moved on — mask them
                    for row_ix, (rev, row) in enumerate(live):
                        if ns.id_rev.get(row[_ROW_ID]) != rev:
                            seg.dead.add(row_ix)
                    ns.segments.append(seg)
                    ns.segments.sort(key=lambda s: s.min_rev)
                # the sealed prefix is now redundant with the segment —
                # record the revision watermark, then reclaim its WAL
                # files; a crash in between replays nothing because
                # recovery skips revs at or below the sealed max/floor
                del ns.tail[:cut]
                ns.tail_base += cut
                ns.tail_by_id = {
                    row[_ROW_ID]: i
                    for i, row in enumerate(ns.tail)
                    if row is not None
                }
                ns.tail_since = time.monotonic()
                ns.persist_rev_floor()
                for p in old_wals:
                    try:
                        os.remove(p)
                    except FileNotFoundError:
                        pass
                ns.sealing = False
        return len(live)

    def compact(self, app_id: int, channel_id: Optional[int] = None) -> int:
        """Merge small adjacent segments, dropping dead rows; returns the
        number of segments merged away."""
        with self._lock:
            ns = self._namespace(app_id, channel_id)
        return self._compact_ns(ns)

    def _compact_ns(self, ns: _Namespace) -> int:
        """Merge adjacent small segments, dropping dead rows. The merge
        build reads only immutable segments and runs outside the store
        lock; the swap is atomic and re-checks liveness (a delete that
        landed mid-merge masks its row in the merged segment). Only
        ADJACENT runs merge — a non-adjacent merge would produce
        overlapping revision ranges and break the binary-searched
        rev → segment lookup."""
        with self._lock:
            if ns.compacting or ns.removed:
                return 0
            runs: list[list[_Segment]] = []
            cur: list[_Segment] = []
            for seg in ns.segments:
                if seg.n_rows <= self.compact_max_rows:
                    cur.append(seg)
                else:
                    if len(cur) > 1:
                        runs.append(cur)
                    cur = []
            if len(cur) > 1:
                runs.append(cur)
            if not runs:
                return 0
            ns.compacting = True
        removed = 0
        try:
            for run in runs:
                # merged rows in revision order; revision VALUES are
                # preserved so tail cursors and the signature stay valid
                rows: list[list] = []
                revs: list[int] = []
                for seg in run:
                    dead = seg.dead
                    rev_col = seg.col("rev")
                    for i in range(seg.n_rows):
                        if i not in dead:
                            rows.append(seg.row(i))
                            revs.append(int(rev_col[i]))
                merged_path = (
                    _write_segment(ns.path, rows, revs) if rows else None
                )
                with self._lock:
                    if ns.removed:
                        if merged_path is not None:
                            shutil.rmtree(merged_path, ignore_errors=True)
                        return removed
                    keep = [s for s in ns.segments if s not in run]
                    if merged_path is not None:
                        merged = _Segment(merged_path)
                        for row_ix, (row, rev) in enumerate(zip(rows, revs)):
                            if ns.id_rev.get(row[_ROW_ID]) != rev:
                                merged.dead.add(row_ix)
                        keep.append(merged)
                    keep.sort(key=lambda s: s.min_rev)
                    ns.segments = keep
                    for seg in run:
                        shutil.rmtree(seg.path, ignore_errors=True)
                removed += len(run) - (1 if rows else 0)
        finally:
            with self._lock:
                ns.compacting = False
        return removed

    def segment_stats(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> dict[str, Any]:
        """Operator surface (`pio status`): segment/tail shape of one
        namespace."""
        with self._lock:
            ns = self._namespace(app_id, channel_id)
            return {
                "segments": len(ns.segments),
                "sealed_rows": sum(s.n_rows for s in ns.segments),
                "dead_rows": sum(len(s.dead) for s in ns.segments),
                "tail_rows": len(ns.tail_by_id),
                "max_revision": ns.next_rev - 1,
                "tombstones": len(ns.tombstones),
            }

    # -- replication seam --------------------------------------------------
    def set_commit_hook(self, hook) -> None:
        """Install (or clear, with None) the synchronous replication
        commit hook. See insert_batch for the calling contract."""
        with self._lock:
            self._commit_hook = hook

    def ship_namespaces(self) -> list[tuple[int, Optional[int]]]:
        """Every (app_id, channel_id) this store holds — loaded ones
        plus on-disk directories not opened yet (the shipper must see
        namespaces it never wrote to in this process)."""
        with self._lock:
            keys = set(self._ns)
        try:
            names = os.listdir(self.base)
        except FileNotFoundError:
            names = []
        for n in names:
            if not n.startswith("app_"):
                continue
            parts = n.split("_")
            try:
                app = int(parts[1])
                ch = int(parts[2]) if len(parts) > 2 else None
            except (IndexError, ValueError):
                continue
            keys.add((app, ch))
        return sorted(keys, key=lambda k: (k[0], k[1] is not None, k[1] or 0))

    def ship_state(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> dict[str, Any]:
        """Shipper-side snapshot of one namespace: watermark, sealed
        segment names with ranges, and the tombstone op counter."""
        with self._lock:
            ns = self._namespace(app_id, channel_id)
            return {
                "watermark": ns.next_rev - 1,
                "tail_floor": ns.tail_base - 1,
                "segments": {
                    os.path.basename(s.path): [s.min_rev, s.max_rev]
                    for s in ns.segments
                },
                "tombstone_ops": ns.delete_ops,
            }

    def ship_tail_after(
        self,
        app_id: int,
        channel_id: Optional[int],
        after_rev: int,
        limit: int,
    ) -> dict[str, Any]:
        """Live unsealed rows with revision > after_rev, revision order,
        at most `limit`. `floor` is the last sealed revision — when it
        exceeds after_rev the follower is missing sealed rows that only
        segment shipping can provide, so the caller must sync segments
        first. Row lists are append-only after publication (supersede
        nulls the slot instead of mutating), so handing references out
        for serialization is safe."""
        with self._lock:
            ns = self._namespace(app_id, channel_id)
            revs: list[int] = []
            rows: list[list] = []
            for rev, row in ns.live_tail():
                if rev <= after_rev:
                    continue
                revs.append(rev)
                rows.append(row)
                if len(revs) >= limit:
                    break
            return {
                "revs": revs,
                "rows": rows,
                "head": ns.next_rev - 1,
                "floor": ns.tail_base - 1,
            }

    def ship_segment_path(
        self, app_id: int, channel_id: Optional[int], name: str
    ) -> Optional[str]:
        """Path of a registered sealed segment by name, or None if it
        was compacted away (the next pass ships the merged segment)."""
        with self._lock:
            ns = self._namespace(app_id, channel_id)
            for seg in ns.segments:
                if os.path.basename(seg.path) == name:
                    return seg.path
        return None

    def ship_tombstones(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> tuple[dict[str, int], int]:
        with self._lock:
            ns = self._namespace(app_id, channel_id)
            return dict(ns.tombstones), ns.delete_ops

    # -- columnar fast path ------------------------------------------------
    @staticmethod
    def _frame_key(
        query: EventQuery, value_prop: Optional[str], default_value: float
    ) -> tuple:
        return (
            (query.app_id, query.channel_id),
            query.start_time, query.until_time, query.entity_type,
            tuple(query.event_names) if query.event_names else None,
            query.target_entity_type, query.filter_target_absent,
            query.shard, value_prop, default_value,
        )

    @staticmethod
    def _sealed_rows(
        snapshot: Sequence[tuple[_Segment, frozenset]],
        query: EventQuery,
        value_prop: Optional[str],
        default_value: float,
    ) -> dict[str, np.ndarray]:
        """Filtered row arrays of every sealed segment, concatenated:
        {time_ms, ids, names, etypes, ents, ttypes, tgts, tgt_ok, values}
        as numpy arrays — mmap + column concat + vectorized remap, no
        per-row Python. Pure function of the (segment, dead-set)
        snapshot, so it runs WITHOUT the store lock: a cold
        training-corpus materialization must not stall ingest acks."""
        parts: list[dict[str, np.ndarray]] = []
        for seg, dead in snapshot:
            mask = np.ones(seg.n_rows, dtype=bool)
            if dead:
                mask[np.fromiter(dead, dtype=np.int64)] = False
            times = seg.col("time_ms")
            if query.start_time is not None:
                mask &= times >= _ms(query.start_time)
            if query.until_time is not None:
                mask &= times < _ms(query.until_time)
            names_v = seg.vocab_np("event_names")
            codes = seg.col("event_code")
            if query.event_names is not None:
                keep_codes = [
                    i for i, n in enumerate(seg.footer["event_names"])
                    if n in query.event_names
                ]
                mask &= np.isin(codes, keep_codes)
            if query.entity_type is not None:
                try:
                    et_code = seg.footer["entity_types"].index(
                        query.entity_type
                    )
                    mask &= seg.col("etype_code") == et_code
                except ValueError:
                    mask[:] = False
            tgt = seg.col("target_idx")
            if query.filter_target_absent:
                mask &= tgt < 0
            elif query.target_entity_type is not None:
                try:
                    tt_code = seg.footer["target_types"].index(
                        query.target_entity_type
                    )
                    mask &= seg.col("ttype_code") == tt_code
                except ValueError:
                    mask[:] = False
            ent = seg.col("entity_idx")
            if query.shard is not None:
                sidx, n_sh = query.shard
                # shard hash per UNIQUE entity (vocab-sized, not
                # row-sized), then a vectorized row lookup
                vocab_shard = np.fromiter(
                    (
                        base.shard_of(eid, n_sh) == sidx
                        for eid in seg.footer["entity_ids"]
                    ),
                    dtype=bool,
                    count=len(seg.footer["entity_ids"]),
                )
                mask &= vocab_shard[ent]
            idx = np.nonzero(mask)[0]
            if not len(idx):
                continue
            if value_prop is None:
                values = np.full(len(idx), default_value, np.float32)
            else:
                col = seg.value_col(value_prop)
                if col is not None:
                    v = np.asarray(col[idx], np.float32)
                    values = np.where(np.isnan(v), default_value, v)
                else:
                    # prop not columnized in this segment (non-numeric
                    # somewhere, or past the column cap): sidecar fallback
                    rows = seg.sidecar_rows()
                    values = np.fromiter(
                        (
                            default_value
                            if (
                                v := DataMap(rows[i][0]).get_opt(
                                    value_prop, float
                                )
                            ) is None
                            else v
                            for i in idx
                        ),
                        np.float32,
                        count=len(idx),
                    )
            tgt_i = tgt[idx]
            tgt_ok = tgt_i >= 0
            parts.append({
                "time_ms": np.asarray(times[idx], np.int64),
                "ids": seg.ids_np()[idx],
                "names": names_v[codes[idx]],
                "etypes": seg.vocab_np("entity_types")[
                    seg.col("etype_code")[idx]
                ],
                "ents": seg.vocab_np("entity_ids")[ent[idx]],
                "ttypes": seg.vocab_np("target_types")[
                    np.maximum(seg.col("ttype_code")[idx], 0)
                ],
                "ttype_ok": seg.col("ttype_code")[idx] >= 0,
                "tgts": seg.vocab_np("target_ids")[np.maximum(tgt_i, 0)],
                "tgt_ok": tgt_ok,
                "values": values,
            })
        if parts:
            return {
                k: np.concatenate([p[k] for p in parts])
                for k in parts[0]
            }
        return _empty_arrays()

    @staticmethod
    def _tail_rows(
        tail: Sequence[tuple[int, list]],
        query: EventQuery,
        value_prop: Optional[str],
        default_value: float,
    ) -> dict[str, np.ndarray]:
        """The unsealed tail as row arrays — the only per-row loop on the
        frame path, bounded by the seal threshold."""
        sel: list[list] = []
        t0 = _ms(query.start_time) if query.start_time else None
        t1 = _ms(query.until_time) if query.until_time else None
        names = (
            set(query.event_names) if query.event_names is not None else None
        )
        for _rev, r in tail:
            if t0 is not None and r[_ROW_TIME] < t0:
                continue
            if t1 is not None and r[_ROW_TIME] >= t1:
                continue
            if names is not None and r[_ROW_EVENT] not in names:
                continue
            if (
                query.entity_type is not None
                and r[_ROW_ETYPE] != query.entity_type
            ):
                continue
            if query.filter_target_absent:
                if r[_ROW_TTYPE] is not None or r[_ROW_TID] is not None:
                    continue
            elif (
                query.target_entity_type is not None
                and r[_ROW_TTYPE] != query.target_entity_type
            ):
                continue
            if not query.shard_matches(r[_ROW_EID]):
                continue
            sel.append(r)
        if not sel:
            return _empty_arrays()
        values = []
        for r in sel:
            v = (
                DataMap(r[_ROW_PROPS]).get_opt(value_prop, float)
                if value_prop is not None
                else None
            )
            values.append(default_value if v is None else v)
        return {
            "time_ms": np.asarray([r[_ROW_TIME] for r in sel], np.int64),
            "ids": np.asarray([r[_ROW_ID] for r in sel], dtype=str),
            "names": np.asarray([r[_ROW_EVENT] for r in sel], dtype=str),
            "etypes": np.asarray([r[_ROW_ETYPE] for r in sel], dtype=str),
            "ents": np.asarray([r[_ROW_EID] for r in sel], dtype=str),
            "ttypes": np.asarray(
                [r[_ROW_TTYPE] or "" for r in sel], dtype=str
            ),
            "ttype_ok": np.asarray(
                [r[_ROW_TTYPE] is not None for r in sel], bool
            ),
            "tgts": np.asarray([r[_ROW_TID] or "" for r in sel], dtype=str),
            "tgt_ok": np.asarray(
                [r[_ROW_TID] is not None for r in sel], bool
            ),
            "values": np.asarray(values, np.float32),
        }

    @staticmethod
    def _first_seen_codes(
        keys: np.ndarray, valid: Optional[np.ndarray] = None
    ) -> tuple[np.ndarray, dict[str, int]]:
        """Vectorized BiMap.string_int over string arrays: dense codes
        in first-seen order. Returns (codes int32 — -1 where invalid,
        vocab dict). Thin adapter over the shared _rank_first_seen."""
        sel = keys[valid] if valid is not None else keys
        if not len(sel):
            return (
                np.full(len(keys), -1, np.int32)
                if valid is not None
                else np.zeros(0, np.int32)
            ), {}
        vocab_list, codes_sel = _rank_first_seen(sel)
        vocab = {v: j for j, v in enumerate(vocab_list)}
        if valid is None:
            return codes_sel, vocab
        codes = np.full(len(keys), -1, np.int32)
        codes[valid] = codes_sel
        return codes, vocab

    def find_frame(
        self,
        query: EventQuery,
        value_prop: Optional[str] = None,
        default_value: float = 1.0,
    ):
        """Columnar training read, bit-identical to
        ``EventFrame.from_events(self.find(query), ...)``: rows ordered
        by (event_time, event_id), vocabs in first-seen order over that
        stream — but assembled by column concat + vectorized remap over
        the sealed segments (cached by segment ids) plus a bounded tail
        loop."""
        if self._exotic(query):
            from predictionio_tpu.data.store.columnar import EventFrame

            return EventFrame.from_events(
                self.find(query),
                value_prop=value_prop,
                default_value=default_value,
            )
        arrays, _n_sealed, _token = self._frame_arrays(
            query, value_prop, default_value
        )
        order = np.lexsort((arrays["ids"], arrays["time_ms"]))
        arrays = {k: v[order] for k, v in arrays.items()}
        return self._arrays_to_frame(arrays)

    @staticmethod
    def _exotic(query: EventQuery) -> bool:
        """Filters the vectorized sealed-row path does not push down
        (entity/target point lookups, keyset cursors, limits, reversed
        scans) — rare on training reads; they take the row fallback."""
        return (
            query.entity_id is not None
            or query.target_entity_id is not None
            or query.start_after is not None
            or query.limit is not None
            or query.reversed
        )

    def find_frame_parts(
        self,
        query: EventQuery,
        value_prop: Optional[str] = None,
        default_value: float = 1.0,
    ):
        """Loader-facing variant: same frame CONTENT, but rows laid out
        sealed-block-first (revision order) so a device stager can cache
        the sealed prefix keyed by the returned segment token and stage
        only the tail on the next retrain. Returns
        (frame, segment_token, n_sealed_rows). Vocab codes of the sealed
        prefix are stable across tail-only growth (first-seen order over
        an unchanged prefix)."""
        if self._exotic(query):
            raise StorageError(
                "find_frame_parts supports training-shaped queries only "
                "(no entity/target point filter, cursor, limit, reversed)"
            )
        arrays, n_sealed, token = self._frame_arrays(
            query, value_prop, default_value
        )
        return self._arrays_to_frame(arrays), token, n_sealed

    def _frame_arrays(
        self, query: EventQuery, value_prop, default_value
    ) -> tuple[dict[str, np.ndarray], int, tuple]:
        key = self._frame_key(query, value_prop, default_value)
        # ONE lock hold snapshots a coherent (segments, dead sets, tail)
        # view; the corpus-sized materialization below runs unlocked
        with self._lock:
            ns = self._namespace(query.app_id, query.channel_id)
            snapshot = [(s, frozenset(s.dead)) for s in ns.segments]
            token = (
                tuple(s.path for s, _d in snapshot),
                sum(len(d) for _s, d in snapshot),
                ns.delete_ops,
            )
            tail_rows = ns.live_tail()
            cached = self._frame_cache.get(key)
        if cached is not None and cached[0] == token:
            self.frame_cache_stats["hits"] += 1
            sealed = cached[1]
        else:
            self.frame_cache_stats["misses"] += 1
            sealed = self._sealed_rows(
                snapshot, query, value_prop, default_value
            )
            with self._lock:
                # bounded: each entry holds corpus-sized arrays, and a
                # rolling training window (fresh start_time per retrain)
                # would otherwise accumulate one dead entry per run
                # until OOM — LRU over query shapes, newest last
                self._frame_cache.pop(key, None)
                self._frame_cache[key] = (token, sealed)
                while len(self._frame_cache) > 8:
                    self._frame_cache.pop(next(iter(self._frame_cache)))
        tail = self._tail_rows(tail_rows, query, value_prop, default_value)
        n_sealed = len(sealed["time_ms"])
        if not len(tail["time_ms"]):
            return dict(sealed), n_sealed, token
        if not n_sealed:
            return tail, 0, token
        merged = {}
        for k in sealed:
            a, b = sealed[k], tail[k]
            if a.dtype.kind == "U" and b.dtype.kind == "U":
                # unify string widths before concat (be explicit rather
                # than relying on numpy's promotion rules)
                width = max(a.dtype.itemsize, b.dtype.itemsize) // 4
                a = a.astype(f"U{max(width, 1)}")
                b = b.astype(f"U{max(width, 1)}")
            merged[k] = np.concatenate([a, b])
        return merged, n_sealed, token

    def _arrays_to_frame(self, arrays: dict[str, np.ndarray]):
        from predictionio_tpu.data.store.bimap import BiMap
        from predictionio_tpu.data.store.columnar import EventFrame

        event_code, ev_vocab = self._first_seen_codes(arrays["names"])
        entity_idx, ent_vocab = self._first_seen_codes(arrays["ents"])
        target_idx, tgt_vocab = self._first_seen_codes(
            arrays["tgts"], valid=arrays["tgt_ok"]
        )
        etype = (
            str(arrays["etypes"][0]) if len(arrays["etypes"]) else None
        )
        ttype = None
        if len(arrays["ttype_ok"]):
            tt_at = np.nonzero(arrays["ttype_ok"])[0]
            if len(tt_at):
                ttype = str(arrays["ttypes"][tt_at[0]])
        return EventFrame(
            event_code=event_code,
            entity_idx=entity_idx,
            target_idx=target_idx,
            time_ms=np.asarray(arrays["time_ms"], np.int64),
            value=np.asarray(arrays["values"], np.float32),
            event_vocab=BiMap(ev_vocab),
            entity_vocab=BiMap(ent_vocab),
            target_vocab=BiMap(tgt_vocab),
            entity_type=etype,
            target_entity_type=ttype,
        )


def _empty_arrays() -> dict[str, np.ndarray]:
    return {
        "time_ms": np.zeros(0, np.int64),
        "ids": np.zeros(0, dtype=str),
        "names": np.zeros(0, dtype=str),
        "etypes": np.zeros(0, dtype=str),
        "ents": np.zeros(0, dtype=str),
        "ttypes": np.zeros(0, dtype=str),
        "ttype_ok": np.zeros(0, bool),
        "tgts": np.zeros(0, dtype=str),
        "tgt_ok": np.zeros(0, bool),
        "values": np.zeros(0, np.float32),
    }
