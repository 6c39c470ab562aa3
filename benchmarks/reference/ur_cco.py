"""Plain float64 reference of the Universal Recommender's train: Mahout's
downsampling, the cross-occurrence counts of sampled primary items from the
raw event lists, and Dunning's log-likelihood ratio in float64.

It shares no code with `predictionio_tpu/models/cco.py`: the draw is
written again from the configuration's text (`downsampling` in
`configs/ur-taobao-userbehavior-train.json`), the events are binarised with
`np.unique`, and an item's co-occurrences are counted by walking its buyers'
event lists.
"""

from __future__ import annotations

import numpy as np

M32 = np.uint64(0xFFFFFFFF)


def fmix32(h):
    """murmur3's 32-bit finaliser, in uint64 arithmetic masked to 32 bits."""
    h = np.asarray(h, np.uint64)
    h = h ^ (h >> np.uint64(16))
    h = (h * np.uint64(0x85EBCA6B)) & M32
    h = h ^ (h >> np.uint64(13))
    h = (h * np.uint64(0xC2B2AE35)) & M32
    return h ^ (h >> np.uint64(16))


def draw(seed: int, indicator: int, rows, cols):
    """The configuration's draw: seed = hi * 2^32 + lo, salt =
    fmix32(fmix32(fmix32(indicator) ^ hi) ^ lo), draw =
    fmix32(fmix32(salt ^ row) ^ col)."""
    hi, lo = (int(seed) % 2**64) >> 32, int(seed) & 0xFFFFFFFF
    salt = fmix32(fmix32(fmix32(indicator) ^ np.uint64(hi)) ^ np.uint64(lo))
    return fmix32(fmix32(np.asarray(rows, np.uint64) ^ salt)
                  ^ np.asarray(cols, np.uint64))


def distinct(rows, cols, n_cols: int):
    """(rows, cols) of the distinct pairs, sorted by user then item."""
    keys = np.unique(np.asarray(rows, np.int64) * n_cols + cols)
    return keys // n_cols, keys % n_cols


def downsample(rows, cols, n_users: int, n_cols: int, cap: int, seed: int,
               indicator: int):
    """Mahout's sampleDownAndBinarize: each distinct (user, item) kept with
    probability min(1, cap / n_user, cap / n_item) over the distinct counts,
    i.e. iff draw * max(n_user, n_item) < cap * 2^32."""
    r, c = distinct(rows, cols, n_cols)
    n_user = np.bincount(r, minlength=n_users)
    n_item = np.bincount(c, minlength=n_cols)
    most = np.maximum(n_user[r], n_item[c]).astype(np.uint64)
    keep = draw(seed, indicator, r, c) * most < (np.uint64(cap) << np.uint64(32))
    return r[keep], c[keep]


def llr(k11, r, c, n):
    """Dunning's LLR, float64, from integer cells: 2 * sum k log(k / E) over
    the four cells of the 2x2 table, an empty cell 0. d = k11 n - r c is
    exact in int64: the table is independent (LLR 0) iff d = 0, and every
    other table has a positive LLR, however small."""
    k11, r, c = (np.asarray(x, np.int64) for x in (k11, r, c))
    n = np.int64(n)
    d = k11 * n - r * c
    cells = (
        (k11, r * c),
        (r - k11, r * (n - c)),
        (c - k11, (n - r) * c),
        (n - r - c + k11, (n - r) * (n - c)),
    )
    total = np.zeros(k11.shape, np.float64)
    for k, en in cells:  # en = E * n
        kf = k.astype(np.float64)
        # log(k / E) = log1p((k n - E n) / (E n)), with k n - E n exact-ish:
        # each cell's deviation is +-d
        dev = (k * n - en).astype(np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            term = kf * np.log1p(dev / en.astype(np.float64))
        total += np.where(k > 0, term, 0.0)
    return np.where((d != 0) & (k11 > 0), np.maximum(2.0 * total, 1e-300), 0.0)


class Reference:
    """The downsampled events of every indicator, their totals, and the
    reference rows of sampled primary items."""

    def __init__(self, cfg: dict, events: dict):
        algo = cfg["algorithm"]
        self.n_users = int(cfg["n_users"])
        self.n_items = int(cfg["n_items"])
        self.top_n = int(algo["max_correlators_per_item"])
        self.names = list(cfg["indicators"])
        seed = int(cfg["downsampling"]["seed"])
        cap = int(algo["max_events_per_event_type"])
        self.kept = {}
        for m, name in enumerate(self.names):
            rows, cols = events[name]
            r, c = downsample(rows, cols, self.n_users, self.n_items, cap,
                              seed, m)
            ptr = np.searchsorted(r, np.arange(self.n_users + 1))
            self.kept[name] = (r, c, ptr, np.bincount(c, minlength=self.n_items))
        pr, pc, _ptr, _tot = self.kept[self.names[0]]
        order = np.argsort(pc, kind="stable")
        self._buyers = (pr[order], np.searchsorted(
            pc[order], np.arange(self.n_items + 1)))

    def row(self, name: str, item: int, scorer=llr):
        """(things, scores) of every correlator candidate of `item` in
        indicator `name` — co-occurring (count > 0) and scored above 0 —
        scores descending, ties by thing. `scorer(k11, r, c, n)` scores
        the counts: the float64 LLR, or a control's form."""
        buyers, at = self._buyers
        users = buyers[at[item]: at[item + 1]]
        _r, c, ptr, totals = self.kept[name]
        lists = [c[ptr[u]: ptr[u + 1]] for u in users]
        if not lists:
            return np.zeros(0, np.int64), np.zeros(0)
        things, k11 = np.unique(np.concatenate(lists), return_counts=True)
        if name == self.names[0]:  # the diagonal is no correlator
            keep = things != item
            things, k11 = things[keep], k11[keep]
        prim_total = self.kept[self.names[0]][3][item]
        scores = np.asarray(scorer(k11, np.full(k11.shape, prim_total),
                                   totals[things], self.n_users), np.float64)
        live = scores > 0
        things, scores = things[live], scores[live]
        order = np.lexsort((things, -scores))
        return things[order], scores[order]


def sample_items(events: dict, cfg: dict, seed: int, top: int = 200,
                 uniform: int = 1800) -> np.ndarray:
    """The primary items a comparison reads: the `top` with the most
    distinct buyers and `uniform` drawn without replacement from the rest
    that were bought (all of them where fewer), from the raw events."""
    rows, cols = events[cfg["indicators"][0]]
    _r, c = distinct(rows, cols, int(cfg["n_items"]))
    buyers = np.bincount(c, minlength=int(cfg["n_items"]))
    order = np.lexsort((np.arange(buyers.size), -buyers))
    order = order[buyers[order] > 0]
    head, rest = order[:top], order[top:]
    rng = np.random.default_rng([seed % (2**32), 3911])
    tail = rng.choice(rest, min(uniform, rest.size), replace=False)
    return np.sort(np.concatenate([head, tail]))


def compare_rows(ref_rows: dict, served: dict, top_n: int,
                 tol: float = 1e-4) -> dict:
    """Numbers of one model's sampled rows against the reference's.
    `ref_rows[name][item]` = (things, scores); `served[name]` = (items,
    idx (n, top_n), scores (n, top_n)) for the sampled items.

    score_gap: largest |s - s_ref| / max(1, s_ref) over the returned
    pairs (s_ref 0 for a thing that is no candidate); set_gap: share of
    the returned pairs whose s_ref lies below the reference's top_n-th
    score by more than `tol` of max(1, that score) (a tie is in);
    count_gap: rows whose number of correlators is not min(top_n,
    candidates); malformed: rows whose -1 padding is not a tail, whose
    scores do not descend, or that repeat a thing."""
    score_gap, below, returned, count_gap, malformed = 0.0, 0, 0, 0, 0
    for name, (items, idx, scores) in served.items():
        for item, ids, sc in zip(items, idx, scores):
            things, ref = ref_rows[name][int(item)]
            valid = ids >= 0
            n = int(valid.sum())
            if not valid[:n].all() or len(set(ids[:n].tolist())) != n or (
                    n > 1 and np.any(np.diff(sc[:n]) > 0)):
                malformed += 1
            count_gap += n != min(top_n, things.size)
            if not n:
                continue
            lookup = dict(zip(things.tolist(), ref.tolist()))
            s_ref = np.array([lookup.get(int(t), 0.0) for t in ids[:n]])
            s = sc[:n].astype(np.float64)
            score_gap = max(score_gap, float(np.max(
                np.abs(s - s_ref) / np.maximum(1.0, s_ref))))
            last = ref[min(top_n, ref.size) - 1] if ref.size else 0.0
            below += int(np.sum(last - s_ref > tol * max(1.0, last)))
            returned += n
    return {
        "cco_score_gap": score_gap,
        "cco_set_gap": below / max(returned, 1),
        "cco_count_gap": float(count_gap),
        "cco_malformed_rows": float(malformed),
    }


def reference_tables(ref: Reference, items: np.ndarray, scorer=llr) -> dict:
    """{name: (items, idx, scores)} of the reference's own top_n for the
    sampled items: what a correct model serves. `scorer` re-scores the
    counts for the controls (a lower precision, the entropy form)."""
    out = {}
    for name in ref.names:
        idx = np.full((items.size, ref.top_n), -1, np.int64)
        sc = np.zeros((items.size, ref.top_n), np.float64)
        for i, item in enumerate(items):
            things, scores = ref.row(name, int(item), scorer)
            n = min(ref.top_n, things.size)
            idx[i, :n], sc[i, :n] = things[:n], scores[:n]
        out[name] = (items, idx, sc)
    return out


def join_work(ref: Reference) -> dict:
    """What the join has to do, from the reference's own downsampled
    events: {indicator: {"kept", "pairs", "distinct"}} — the primary x
    indicator pairs users make (the diagonal out in the primary's own) and
    the distinct (item, thing) among them. The kept events of both sides
    are the join's input."""
    pr, pc, _pptr, _tot = ref.kept[ref.names[0]]
    out = {}
    for name in ref.names:
        _r, c, ptr, _t = ref.kept[name]
        lens = ptr[pr + 1] - ptr[pr]  # a buy's user's events of this type
        firsts = np.repeat(ptr[pr], lens)
        run_start = np.repeat(np.cumsum(lens) - lens, lens)
        things = c[firsts + np.arange(firsts.size) - run_start]
        keys = np.repeat(pc.astype(np.int64), lens) * ref.n_items + things
        if name == ref.names[0]:
            keys = keys[keys // ref.n_items != things]
        out[name] = {"kept": int(c.size), "pairs": int(keys.size),
                     "distinct": int(np.unique(keys).size)}
    return out
