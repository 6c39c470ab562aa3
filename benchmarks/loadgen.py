"""Query traffic: one general generator, run as a process of its own that
never imports jax (it shares no interpreter lock with the server and cannot
take the chip).

    python3 benchmarks/loadgen.py --url http://127.0.0.1:PORT/queries.json \\
        --traffic FILE.json --n-users N --n-items M --seed S --seconds T \\
        --procs P --index I --start-at EPOCH --out FILE.json

What is sent depends only on --seed and the traffic file: the users are drawn
Zipf(`user_zipf_s`) over the n_users (rank r is user row (r * STRIDE) mod
n_users, so the hot users are spread over the table), `num` per query, and a
`blacklist_share` of the queries carry a blacklist of 1..`blacklist_max` item
ids drawn uniformly. Two loops:

- `"loop": "closed"` — `connections` keep-alive connections, each sending its
  next query when the reply to the last one has come;
- `"loop": "open"` — Poisson-shaped arrivals at `rate_qps`, the schedule made
  from the seed before the window starts (every seed gets the same gaps and
  the same mix in another order: `arrival_times`, `fixed_mix`); a query is
  timed from the instant it was DUE, so a stall is paid by every query
  behind it, and how late the generator itself sent is reported.

With --procs P the traffic is split over P such processes (process I takes
every P-th connection, or a Poisson stream of rate/P), which all start the
window at --start-at. Each writes counts, the latencies of every query, and
a seed-drawn sample of finished queries with their replies (the one with the
longest blacklist among them) for the comparison with the reference.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import resource
import sys
import time
from urllib.parse import urlparse

import numpy as np

STRIDE = 2_654_435_761  # odd, so coprime with any n_users not a multiple


def user_stride(n_users: int) -> int:
    from math import gcd

    s = STRIDE % n_users or 1
    while gcd(s, n_users) != 1:
        s += 1
    return s


_CDFS: dict[tuple[int, float], np.ndarray] = {}


def zipf_cdf(n: int, s: float) -> np.ndarray:
    """P(rank <= r), rank 1..n with weight r^-s; one table per process."""
    if (n, s) not in _CDFS:
        cdf = np.cumsum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** s)
        _CDFS[(n, s)] = cdf / cdf[-1]
    return _CDFS[(n, s)]


class QueryStream:
    """The i-th query of a stream is a function of (seed, stream, i)."""

    def __init__(self, traffic: dict, n_users: int, n_items: int, seed: int,
                 stream: int):
        self.t = traffic
        self.n_users, self.n_items = n_users, n_items
        self.rng = np.random.default_rng([seed % (2**32), 7919, stream])
        self.cdf = zipf_cdf(n_users, float(traffic.get("user_zipf_s", 1.0)))
        self.stride = user_stride(n_users)
        self._buf: list[dict] = []

    def _refill(self, n: int = 4096) -> None:
        t, rng = self.t, self.rng
        ranks = np.searchsorted(self.cdf, rng.random(n), side="right")
        users = (ranks.astype(np.int64) + 1) * self.stride % self.n_users
        has_black = rng.random(n) < float(t.get("blacklist_share", 0.0))
        n_black = rng.integers(1, int(t.get("blacklist_max", 1)) + 1, n)
        for i in range(n):
            q = {"user": f"u{users[i]}", "num": int(t.get("num", 10))}
            if has_black[i]:
                ids = rng.choice(self.n_items, int(n_black[i]), replace=False)
                q["blacklist"] = [f"i{j}" for j in ids]
            self._buf.append(q)
        self._buf.reverse()

    def next(self) -> dict:
        if not self._buf:
            self._refill()
        return self._buf.pop()


def arrival_times(rate: float, seconds: float, seed: int, stream: int):
    """Poisson-shaped arrivals in [0, seconds): offsets in seconds.

    Every seed gets the SAME set of gaps — the n = rate * seconds quantiles of
    the exponential distribution — in another order, so that no seed draws a
    busier or a quieter window than another: two runs then differ by what
    the system did, not by what the draw gave them."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng = np.random.default_rng([seed % (2**32), 104729, stream])
    t = np.cumsum(rng.permutation(gaps))
    t -= t[0] / 2  # the first arrives half its gap in, not a whole one
    return t[t < seconds]


def fixed_mix(n: int, traffic: dict, n_users: int, n_items: int, seed: int,
              stream: int) -> list[dict]:
    """n queries of the open loop: the same popularity profile and the same
    blacklists' sizes for every seed (the Zipf quantiles as ranks, a
    `blacklist_share` of the queries with sizes cycling 1..`blacklist_max`),
    in an order, on users and with item ids that the seed draws."""
    rng = np.random.default_rng([seed % (2**32), 7919, stream])
    cdf = zipf_cdf(n_users, float(traffic.get("user_zipf_s", 1.0)))
    ranks = np.searchsorted(cdf, (np.arange(n) + 0.5) / n, side="right")
    offset = int(rng.integers(0, n_users))
    users = ((ranks.astype(np.int64) + 1) * user_stride(n_users) + offset) % n_users
    n_black = int(round(float(traffic.get("blacklist_share", 0.0)) * n))
    sizes = np.zeros(n, np.int64)
    sizes[:n_black] = np.arange(n_black) % int(traffic.get("blacklist_max", 1)) + 1
    order = rng.permutation(n)
    queries = []
    for i in order:
        q = {"user": f"u{users[i]}", "num": int(traffic.get("num", 10))}
        if sizes[i]:
            ids = rng.choice(n_items, int(sizes[i]), replace=False)
            q["blacklist"] = [f"i{j}" for j in ids]
        queries.append(q)
    return queries


class Connection:
    """One HTTP/1.1 keep-alive connection."""

    def __init__(self, host: str, port: int, path: str):
        self.host, self.port, self.path = host, port, path
        self.reader = self.writer = None

    async def post(self, body: bytes) -> tuple[int, bytes]:
        if self.writer is None:
            self.reader, self.writer = await asyncio.open_connection(
                self.host, self.port)
        head = (
            f"POST {self.path} HTTP/1.1\r\nHost: {self.host}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n"
        ).encode()
        self.writer.write(head + body)
        await self.writer.drain()
        status_line = await self.reader.readline()
        if not status_line:
            raise ConnectionError("server closed the connection")
        status = int(status_line.split()[1])
        length, close = 0, False
        while True:
            line = await self.reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, value = line.partition(b":")
            key = key.strip().lower()
            if key == b"content-length":
                length = int(value)
            elif key == b"connection" and value.strip().lower() == b"close":
                close = True
        data = await self.reader.readexactly(length) if length else b""
        if close:
            self.close()
        return status, data

    def close(self) -> None:
        if self.writer is not None:
            self.writer.close()
        self.reader = self.writer = None


class Recorder:
    def __init__(self):
        self.rows: list[tuple] = []  # (due, sent, done, ok, query, reply)

    def add(self, due, sent, done, status, query, data) -> None:
        ok = False
        reply = None
        if status == 200:
            try:
                reply = json.loads(data)
                ok = isinstance(reply.get("item_scores"), list) and len(
                    reply["item_scores"]) == query["num"]
            except (ValueError, AttributeError):
                ok = False
        if reply is None:
            reply = {"status": status, "body": data[:200].decode("latin1")}
        self.rows.append((due, sent, done, ok, query, reply))


async def one_query(conn: Connection, rec: Recorder, query: dict, due: float,
                    timeout: float) -> None:
    sent = time.time()
    try:
        status, data = await asyncio.wait_for(
            conn.post(json.dumps(query).encode()), timeout)
    except (OSError, asyncio.TimeoutError, asyncio.IncompleteReadError,
            ConnectionError, ValueError) as e:
        conn.close()
        status, data = 0, repr(e).encode()
    rec.add(due, sent, time.time(), status, query, data)


async def closed_loop(args, traffic, rec, t0, t_end) -> None:
    url = urlparse(args.url)
    total = int(traffic["connections"])
    mine = [c for c in range(total) if c % args.procs == args.index]
    timeout = float(traffic.get("reply_timeout_s", 60))

    async def client(c: int) -> None:
        conn = Connection(url.hostname, url.port, url.path)
        qs = QueryStream(traffic, args.n_users, args.n_items, args.seed,
                         1000 + c)
        while time.time() < t_end:
            now = time.time()
            await one_query(conn, rec, qs.next(), now, timeout)
        conn.close()

    await asyncio.sleep(max(0.0, t0 - time.time()))
    await asyncio.gather(*(client(c) for c in mine))


async def open_loop(args, traffic, rec, t0, t_end) -> None:
    url = urlparse(args.url)
    rate = float(traffic["rate_qps"]) / args.procs
    timeout = float(traffic.get("reply_timeout_s", 60))
    offsets = arrival_times(rate, t_end - t0, args.seed, args.index)
    queries = fixed_mix(len(offsets), traffic, args.n_users, args.n_items,
                        args.seed, 2000 + args.index)
    idle: list[Connection] = []
    tasks = []

    async def fire(query, due):
        conn = idle.pop() if idle else Connection(
            url.hostname, url.port, url.path)
        await one_query(conn, rec, query, due, timeout)
        idle.append(conn)

    for off, query in zip(offsets, queries):
        due = t0 + off
        delay = due - time.time()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(fire(query, due)))
    if tasks:
        await asyncio.gather(*tasks)
    for conn in idle:
        conn.close()


def percentile(sorted_values, q: float) -> float:
    if not len(sorted_values):
        return float("nan")
    i = min(len(sorted_values) - 1, int(np.ceil(q * len(sorted_values))) - 1)
    return float(sorted_values[max(i, 0)])


def summarise(args, traffic, rec: Recorder, t0: float, t_end: float,
              cpu_s: float) -> dict:
    rows = rec.rows
    lat = np.array([r[2] - r[0] for r in rows]) if rows else np.zeros(0)
    ok = np.array([r[3] for r in rows], bool) if rows else np.zeros(0, bool)
    done = np.array([r[2] for r in rows]) if rows else np.zeros(0)
    late = np.sort([r[1] - r[0] for r in rows]) if rows else np.zeros(0)
    in_window = done <= t_end
    # the sample for the comparison: drawn from the seed among the finished
    # queries, with the one that has the longest blacklist in it
    finished = [i for i, r in enumerate(rows) if r[3]]
    rng = np.random.default_rng([args.seed % (2**32), 15485863, args.index])
    want = int(np.ceil(int(traffic.get("check_sample", 300)) / args.procs))
    pick = set(rng.choice(len(finished), min(want, len(finished)),
                          replace=False).tolist()) if finished else set()
    chosen = [finished[i] for i in sorted(pick)]
    if finished:
        longest = max(finished,
                      key=lambda i: len(rows[i][4].get("blacklist", ())))
        if longest not in chosen:
            chosen.append(longest)
    return {
        "index": args.index,
        "attempted": len(rows),
        "ok": int(ok.sum()),
        "failed": int((~ok).sum()),
        "ok_in_window": int((ok & in_window).sum()),
        "window_s": t_end - t0,
        "latencies_ms": np.round(lat * 1000.0, 3).tolist(),
        "ok_flags": ok.astype(int).tolist(),
        "lateness_ms": {
            "p50": percentile(late, 0.5) * 1000.0,
            "p99": percentile(late, 0.99) * 1000.0,
            "max": float(late.max() * 1000.0) if len(late) else 0.0,
        },
        "last_done_after_window_s": float(done.max() - t_end) if len(done) else 0.0,
        "generator_cpu_share": cpu_s / max(t_end - t0, 1e-9),
        "first_failures": [rows[i][5] for i in np.flatnonzero(~ok)[:3]],
        "sample": [
            {"query": rows[i][4], "reply": rows[i][5],
             "latency_ms": (rows[i][2] - rows[i][0]) * 1000.0}
            for i in chosen
        ],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--n-users", type=int, required=True)
    ap.add_argument("--n-items", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--procs", type=int, default=1)
    ap.add_argument("--index", type=int, default=0)
    ap.add_argument("--start-at", type=float, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        traffic = json.load(f)
    rec = Recorder()
    t0 = args.start_at if args.start_at is not None else time.time() + 0.2
    t_end = t0 + args.seconds
    loop = {"closed": closed_loop, "open": open_loop}[traffic["loop"]]
    print("ready", flush=True)
    cpu0 = time.process_time()
    asyncio.run(loop(args, traffic, rec, t0, t_end))
    cpu_s = time.process_time() - cpu0
    out = summarise(args, traffic, rec, t0, t_end, cpu_s)
    out["max_rss_mb"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
