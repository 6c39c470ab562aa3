#!/usr/bin/env python3
"""Chip smoke: `pio train` -> `pio deploy` at ML-20M width on the accelerator.

With no argument this REQUIRES an accelerator and drives the system's main
path once, the way a user would, at the full width of the one model the
repo headlines (implicit ALS, 138,493 users x 26,744 items, 20,000,263
unique ratings, rank 10; depth cut to 3 iterations, data made from a seed):

  1. probe     a child asks jax what it sees; no accelerator -> exit != 0
  2. load      the seeded corpus is written as a ratings file (the
               FileRecommendationEngine's input — see CHANGES.md PR 21 for
               why not 20M events through the event store)
  3. train     `python -m predictionio_tpu.tools.console train` as a child,
               waited for until it EXITS (one process per chip)
  4. factors   finite, and observed pairs score above random pairs
  5. deploy    `... console deploy` as a second child; POST /queries.json
               plain / blacklist (row list) / whitelist and a >64-id
               blacklist (packed bits) at batch buckets 1, 8 and 64; every
               answer checked against numpy f32 on the persisted factors;
               no compile after warm-up; then GET /stop
  6. sharded   with more than one device visible: the train above already
               ran over all of them; a `shard_serving: true` variant is
               trained and served through fleet.ShardedRuntime, same checks

It fails (non-zero, reason on stderr, no result line) if any child lands on
another platform than the probe saw, a kernel falls back, warm-up fails, a
score is non-finite or misses the stated tolerance. On success the LAST
stdout line is one JSON object: {"ok": true, "device": {...}}.

`--rehearsal` walks the same control flow tiny on the CPU (Pallas kernels
through the interpreter), says so, and never prints the result line.

This script never imports jax: a parent that touched jax would hold the
chip its children need.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

FULL = dict(n_users=138_493, n_items=26_744, n_events=20_000_263)
TINY = dict(n_users=700, n_items=500, n_events=40_000)
RANK = 10
ITERATIONS = 3
NUM = 10  # results per query
SEED = 0

# Served scores vs the numpy f32 reference `uf[u] @ itf.T` on the persisted
# factors. The default serving state stores f32, but on the TPU the MXU
# runs a default-precision f32 dot on bf16-rounded operands with f32
# accumulation — the fused kernel at every batch size, the XLA two-step
# from batch 8 up (tests/chip_parity.py; measured on the v5e in PR 21, see
# PERF.md: largest deviation from numpy 2^-8.1 of the row's largest
# sum_k |u_k x_k|). A bf16
# operand is within 2^-9 of its value when rounded and 2^-8 when
# truncated, two operands per product, so a served score lies within
# 2^-8 (rounded) or 2^-7 (truncated) * sum_k |u_k x_k| of the reference;
# the bound is 2^-6, twice the worse case. Ranking is held to the same
# bound: every served item must score, by the reference, within it of
# the reference's own num-th best.
SCORE_REL_TOL = 2.0 ** -6

TRAIN_TIMEOUT_S = 700
LIVE_TIMEOUT_S = 300
QUERY_TIMEOUT_S = 60


class SmokeFailure(Exception):
    pass


def phase(name: str, seconds: float, note: str = "") -> None:
    print(f"[smoke] {name}: {seconds:.2f}s {note}".rstrip(), flush=True)


# ---------------------------------------------------------------------------
# corpus
# ---------------------------------------------------------------------------


def make_corpus(n_users: int, n_items: int, n_events: int, seed: int):
    """The corpus recipe — dirichlet(0.3) popularity on both sides,
    UNIQUE (user, item) pairs, ratings 1..5 — plus one guaranteed pair per
    user and per item: the trained width is what the data source sees, so
    a user the draw never picked would silently narrow the model."""
    rng = np.random.RandomState(seed)
    user_p = rng.dirichlet(np.full(n_users, 0.3))
    item_p = rng.dirichlet(np.full(n_items, 0.3))
    cover_u = np.arange(n_users, dtype=np.int64) * n_items + rng.choice(
        n_items, n_users, p=item_p
    )
    cover_i = rng.choice(n_users, n_items, p=user_p).astype(
        np.int64
    ) * n_items + np.arange(n_items, dtype=np.int64)
    cover = np.unique(np.concatenate([cover_u, cover_i]))
    keys = cover
    while keys.size < n_events:
        draw = int((n_events - keys.size) * 1.15) + 1000
        r = rng.choice(n_users, draw, p=user_p).astype(np.int64)
        c = rng.choice(n_items, draw, p=item_p).astype(np.int64)
        keys = np.unique(np.concatenate([keys, r * n_items + c]))
    extra = np.setdiff1d(keys, cover, assume_unique=True)
    rng.shuffle(extra)
    keys = np.concatenate([cover, extra[: n_events - cover.size]])
    rng.shuffle(keys)
    rows = (keys // n_items).astype(np.int32)
    cols = (keys % n_items).astype(np.int32)
    vals = rng.randint(1, 6, n_events).astype(np.int8)
    return rows, cols, vals


def write_ratings(path: str, rows, cols, vals) -> None:
    chunk = 1_000_000
    with open(path, "w") as f:
        for lo in range(0, len(rows), chunk):
            f.write("".join(
                f"u{u}::i{i}::{r}\n"
                for u, i, r in zip(
                    rows[lo:lo + chunk].tolist(),
                    cols[lo:lo + chunk].tolist(),
                    vals[lo:lo + chunk].tolist(),
                )
            ))


def write_variant(path: str, variant_id: str, ratings: str,
                  shard_serving: bool) -> None:
    algo = {
        "rank": RANK, "num_iterations": ITERATIONS, "lambda_": 0.01,
        "alpha": 1.0, "implicit_prefs": True, "seed": 3,
    }
    if shard_serving:
        algo["shard_serving"] = True
    with open(path, "w") as f:
        json.dump({
            "id": variant_id,
            "engineFactory": "predictionio_tpu.engines.recommendation."
                             "engine.FileRecommendationEngine",
            "datasource": {"params": {"filepath": ratings}},
            "algorithms": [{"name": "als", "params": algo}],
            # as shipped (engines/recommendation/engine.json): every
            # visible device on the data axis
            "mesh": {"dp": -1, "mp": 1},
        }, f)


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------


class Children:
    """Every process the smoke starts, so that all of them are stopped
    whatever happens."""

    def __init__(self):
        self.procs: list[subprocess.Popen] = []

    def start(self, argv, env, log_path) -> subprocess.Popen:
        log = open(log_path, "wb")
        try:
            proc = subprocess.Popen(
                argv, env=env, cwd=ROOT, stdout=log,
                stderr=subprocess.STDOUT,
            )
        finally:
            log.close()  # the child holds its own descriptor
        self.procs.append(proc)
        return proc

    def stop_all(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=15)


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return "(no log)"


def child_env(base_dir: str, platform: str, rehearsal: bool,
              devices: int) -> dict:
    # the children get the smoke's OWN storage and no inherited PIO_*
    # knob: the dense gate and the kernel modes must resolve from the
    # shape and the device, not from a flag
    env = {k: v for k, v in os.environ.items() if not k.startswith("PIO_")}
    env["PIO_FS_BASEDIR"] = base_dir
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # pinned: jax's own fall-back to another platform is then an error in
    # the child, not a slow pass
    env["JAX_PLATFORMS"] = platform
    if rehearsal:
        from predictionio_tpu.utils.cpuonly import force_cpu_env  # no jax

        env["PIO_PALLAS_RECOMMEND"] = "interpret"
        force_cpu_env(env, devices)
    return env


_PROBE = (
    "import json, jax; d = jax.devices(); "
    "print(json.dumps({'platform': d[0].platform, "
    "'kind': d[0].device_kind, 'count': len(d)}))"
)

# runs on the CPU platform on purpose: it only unpickles the persisted
# model with the repo's own loader and writes the factors out as numpy —
# no device work, so it cannot take the chip from the deploy child
_EXPORT = """
import sys
import numpy as np
from predictionio_tpu.controller.persistent import deserialize_models
from predictionio_tpu.data.storage.registry import Storage
blob = Storage.get_instance().get_model_data_models().get(sys.argv[1])
f = deserialize_models(blob.models)[0].factors
users, items = f.user_vocab.to_dict(), f.item_vocab.to_dict()
np.savez(
    sys.argv[2], uf=f.user_factors, itf=f.item_factors,
    user_ids=np.array(list(users)), user_rows=np.array(list(users.values())),
    item_ids=np.array(list(items)), item_rows=np.array(list(items.values())),
)
"""


def run_probe(children, env, log_path) -> dict:
    proc = children.start([sys.executable, "-c", _PROBE], env, log_path)
    try:
        rc = proc.wait(timeout=180)
    except subprocess.TimeoutExpired:
        raise SmokeFailure("device probe hung:\n" + tail(log_path))
    if rc != 0:
        raise SmokeFailure(
            f"no accelerator: jax pinned to JAX_PLATFORMS="
            f"{env['JAX_PLATFORMS']} found no device (probe rc={rc}):\n"
            + tail(log_path, 6)
        )
    with open(log_path) as f:
        return json.loads(f.read().strip().splitlines()[-1])


def run_train(children, env, variant_path, log_path) -> str:
    proc = children.start(
        [sys.executable, "-m", "predictionio_tpu.tools.console", "train",
         "--engine-json", variant_path],
        env, log_path,
    )
    try:
        rc = proc.wait(timeout=TRAIN_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(
            f"pio train still running after {TRAIN_TIMEOUT_S}s:\n"
            + tail(log_path)
        )
    if rc != 0:
        raise SmokeFailure(f"pio train exited {rc}:\n" + tail(log_path))
    with open(log_path, errors="replace") as f:
        out = f.read()
    for line in out.splitlines():
        if line.startswith("[INFO]"):
            print("        " + line, flush=True)
    marker = "[INFO] Training completed: instance "
    ids = [ln[len(marker):].strip() for ln in out.splitlines()
           if ln.startswith(marker)]
    if not ids:
        raise SmokeFailure("pio train printed no instance id:\n" + out[-2000:])
    return ids[-1]


def check_device(where: str, got: dict, probe: dict) -> None:
    seen = {
        "platform": got.get("platform"),
        "kind": got.get("device_kind"),
        "count": got.get("device_count"),
    }
    if seen != probe:
        raise SmokeFailure(
            f"{where} reports device {seen}, the probe saw {probe}"
        )


def check_train_profile(profile: dict, probe: dict, full: bool) -> dict:
    """The path `pio train` took, from what its profiler registry says RAN.
    Returns the train executable's row."""
    check_device("pio train", profile, probe)
    execs = profile["executables"]
    trains = {n: e for n, e in execs.items() if n.startswith("als.train")}
    if len(trains) != 1:
        raise SmokeFailure(f"expected one ALS train executable: {execs}")
    (name, row), = trains.items()
    if full:
        # 20M unique pairs over a 3.7 GB int8 matrix: the dense gate must
        # have opened by itself, onto every device the mesh spans
        want = ("als.train_dense_sharded" if probe["count"] > 1
                else "als.train_dense")
        static = row["static_kwargs"]
        if name != want or static.get("dense_dtype") != "int8":
            raise SmokeFailure(
                f"train took {name}{static}; the shape implies {want} "
                "with dense_dtype=int8"
            )
    return dict(row, name=name)


# ---------------------------------------------------------------------------
# factors
# ---------------------------------------------------------------------------


def check_factors(model, rows, cols, seed: int) -> None:
    uf, itf = model["uf"], model["itf"]
    if not (np.isfinite(uf).all() and np.isfinite(itf).all()):
        raise SmokeFailure("trained factors are not finite")
    u_row, i_row = model["u_row"], model["i_row"]
    rng = np.random.RandomState(seed + 1)
    pick = rng.choice(len(rows), min(20_000, len(rows)), replace=False)
    ur = np.array([u_row[f"u{u}"] for u in rows[pick].tolist()])
    ir = np.array([i_row[f"i{i}"] for i in cols[pick].tolist()])
    observed = float(np.mean(np.sum(uf[ur] * itf[ir], axis=1)))
    ru = rng.randint(0, uf.shape[0], len(pick))
    ri = rng.randint(0, itf.shape[0], len(pick))
    random_ = float(np.mean(np.sum(uf[ru] * itf[ri], axis=1)))
    print(f"[smoke] factors: finite; mean score observed pairs "
          f"{observed:.4f} vs random pairs {random_:.4f}", flush=True)
    if not observed > random_:
        raise SmokeFailure(
            "observed pairs do not score above random pairs: the train "
            "learned nothing"
        )


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def http(method: str, url: str, body=None, timeout: float = QUERY_TIMEOUT_S):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


def metric(text: str, name: str, labels: str = "", default=None) -> float:
    want = name + labels
    for line in text.splitlines():
        if line.startswith(want + " "):
            return float(line.split()[-1])
    if default is None:
        raise SmokeFailure(f"/metrics has no {want}")
    return default


def batch_buckets(text: str) -> tuple[float, float, float]:
    """Coalesced device batches seen so far in buckets 1 / 2..8 / 9..64
    (the histogram appears with the first batch)."""
    le = [metric(text, "batch_size_bucket", f'{{le="{b}"}}', default=0.0)
          for b in (1, 8, 64)]
    return le[0], le[1] - le[0], le[2] - le[1]


def check_answer(kind, user, excluded, allowed, body, model) -> None:
    uf, itf = model["uf"], model["itf"]
    u = model["u_row"][user]
    ref = itf @ uf[u]
    bound = SCORE_REL_TOL * (np.abs(itf) @ np.abs(uf[u])) + 1e-30
    ok = np.ones(len(ref), bool)
    if allowed is not None:
        ok[:] = False
        ok[[model["i_row"][i] for i in allowed]] = True
    ok[[model["i_row"][i] for i in excluded]] = False
    got = json.loads(body)["item_scores"]
    if len(got) != NUM:
        raise SmokeFailure(f"{kind} query for {user}: {len(got)} results")
    scores = np.array([g["score"] for g in got], np.float64)
    idx = np.array([model["i_row"][g["item"]] for g in got])
    if not np.isfinite(scores).all():
        raise SmokeFailure(f"{kind} query for {user}: non-finite scores")
    if not ok[idx].all():
        raise SmokeFailure(f"{kind} query for {user}: served a filtered item")
    if len(set(idx.tolist())) != NUM or (np.diff(scores) > 0).any():
        raise SmokeFailure(f"{kind} query for {user}: not a sorted top-k")
    err = np.abs(scores - ref[idx])
    if (err > bound[idx]).any():
        w = int(np.argmax(err / bound[idx]))
        raise SmokeFailure(
            f"{kind} query for {user}: served {scores[w]!r} vs numpy "
            f"{ref[idx[w]]!r} — off by {err[w]:.3e}, bound {bound[idx[w]]:.3e}"
        )
    kth = np.sort(ref[ok])[-NUM]
    short = kth - ref[idx]
    slack = bound[idx] + bound[ok].max()
    if (short > slack).any():
        raise SmokeFailure(
            f"{kind} query for {user}: served items rank below the "
            f"reference top-{NUM} by more than the bound"
        )


def serve_leg(children, env, variant_path, log_path, model, probe,
              expect_mode: str, sharded: bool, seed: int) -> None:
    tag = "sharded" if sharded else "deploy"
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    t0 = time.perf_counter()
    proc = children.start(
        [sys.executable, "-m", "predictionio_tpu.tools.console", "deploy",
         "--engine-json", variant_path, "--ip", "127.0.0.1",
         "--port", str(port)],
        env, log_path,
    )
    # live == model loaded, staged, and warm-up ran every serving program
    while True:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"pio deploy exited {proc.returncode} before it was live "
                "(a failed warm-up ends here):\n" + tail(log_path)
            )
        with open(log_path, errors="replace") as f:
            if "Engine API is live" in f.read():
                break
        if time.perf_counter() - t0 > LIVE_TIMEOUT_S:
            raise SmokeFailure("pio deploy not live in time:\n" + tail(log_path))
        time.sleep(0.5)
    phase(f"{tag} warm-up", time.perf_counter() - t0,
          "(process start + model load + staging + every serving compile)")
    with open(log_path, errors="replace") as f:
        for line in f.read().splitlines():
            if line.startswith("[INFO]"):
                print("        " + line, flush=True)

    compiles0 = metric(http("GET", base + "/metrics")[1],
                       "jax_jit_compile_count")
    rng = np.random.RandomState(seed + 2)
    users = model["user_ids"]
    items = model["item_ids"]
    # (label, blacklist size, whitelist size): <= 8 ids is the warmed
    # row-list width; a whitelist or > 64 ids ships as packed bit words
    kinds = [
        ("plain", 0, 0), ("blacklist-rows", 5, 0),
        ("whitelist-bits", 0, 200), ("blacklist-bits", 100, 0),
    ]
    n_items = len(items)
    for label, n_black, n_white in kinds:
        lat = []
        # 1 alone; 8 and 48 in flight coalesce into buckets 8 and 64
        # (the dispatcher sends the first arrival alone when idle)
        for burst in (1, 8, 48):
            for attempt in range(4):
                before = batch_buckets(http("GET", base + "/metrics")[1])
                results: list = [None] * burst
                queries = []
                for _ in range(burst):
                    user = str(users[rng.randint(len(users))])
                    black = [str(x) for x in rng.choice(
                        items, min(n_black, n_items // 4), replace=False)]
                    white = [str(x) for x in rng.choice(
                        items, min(n_white, n_items // 2), replace=False)
                    ] if n_white else None
                    q = {"user": user, "num": NUM}
                    if black:
                        q["blacklist"] = black
                    if white is not None:
                        q["whitelist"] = white
                    queries.append((user, black, white, q))

                def post(j):
                    t1 = time.perf_counter()
                    try:
                        results[j] = http(
                            "POST", base + "/queries.json", queries[j][3]
                        ) + (time.perf_counter() - t1,)
                    except urllib.error.HTTPError as e:
                        results[j] = (e.code, e.read().decode(), 0.0)
                    except Exception as e:  # reported per query below
                        results[j] = (0, repr(e), 0.0)

                threads = [threading.Thread(target=post, args=(j,))
                           for j in range(burst)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=QUERY_TIMEOUT_S + 10)
                for (user, black, white, _q), res in zip(queries, results):
                    if res is None or res[0] != 200:
                        raise SmokeFailure(
                            f"{label} query for {user} -> {res}\n"
                            + tail(log_path)
                        )
                    check_answer(label, user, black, white, res[1], model)
                    lat.append(res[2])
                after = batch_buckets(http("GET", base + "/metrics")[1])
                hit = [a - b for a, b in zip(after, before)]
                want = {1: 0, 8: 1, 48: 2}[burst]
                if hit[want] > 0:
                    break
            else:
                raise SmokeFailure(
                    f"{label}: no device batch landed in bucket "
                    f"{(1, 8, 64)[want]} after 4 bursts of {burst}"
                )
        phase(f"{tag} queries {label}", sum(lat),
              f"({len(lat)} queries, median {np.median(lat) * 1e3:.1f} ms, "
              f"max {max(lat) * 1e3:.1f} ms per query)")

    compiles1 = metric(http("GET", base + "/metrics")[1],
                       "jax_jit_compile_count")
    if compiles1 != compiles0:
        raise SmokeFailure(
            f"{int(compiles1 - compiles0)} compile(s) after warm-up: a "
            "query met a program warm-up did not cover"
        )
    report = json.loads(http("GET", base + "/debug/profile")[1])
    check_device("pio deploy", report["platform"], probe)
    name = "fleet.recommend_sharded" if sharded else "als.recommend_serving"
    row = next(
        (e for e in report["executables"] if e["name"] == name), None
    )
    if row is None:
        raise SmokeFailure(
            f"{name} never ran: " +
            str([e["name"] for e in report["executables"]])
        )
    if row["static_kwargs"].get("mode") != expect_mode:
        raise SmokeFailure(
            f"{name} ran in mode {row['static_kwargs'].get('mode')!r}, "
            f"expected {expect_mode!r} — the fused kernel fell back"
        )
    print(f"[smoke] {tag}: {name} mode={expect_mode} "
          f"invocations={row['invocations']} "
          f"device_seconds={row['device_seconds']} "
          f"compiles after warm-up=0", flush=True)

    http("GET", base + "/stop")
    try:
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        raise SmokeFailure("pio deploy still running 60s after GET /stop")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def run(args, children: Children, scratch: str) -> dict:
    rehearsal = args.rehearsal
    size = TINY if rehearsal else FULL
    platform = "cpu" if rehearsal else "tpu"
    env = child_env(scratch, platform, rehearsal, args.devices)

    t0 = time.perf_counter()
    probe = run_probe(children, env, os.path.join(scratch, "probe.log"))
    phase("probe", time.perf_counter() - t0, json.dumps(probe))
    if probe["platform"] != platform:
        raise SmokeFailure(f"probe landed on {probe['platform']}")

    t0 = time.perf_counter()
    rows, cols, vals = make_corpus(seed=SEED, **size)
    ratings = os.path.join(scratch, "ratings.dat")
    write_ratings(ratings, rows, cols, vals)
    phase("load", time.perf_counter() - t0,
          f"({len(rows)} unique pairs, {size['n_users']} x "
          f"{size['n_items']}, ratings file "
          f"{os.path.getsize(ratings) / 1e6:.0f} MB)")

    # the parent reads each train's EngineInstance row back from the
    # children's store: the zero-config wiring under PIO_FS_BASEDIR
    # (sqlite + localfs — no jax in that import)
    from predictionio_tpu.data.storage.registry import Storage, StorageConfig

    storage = Storage(StorageConfig.default_dev(scratch))
    legs = [("smoke", False)]
    if probe["count"] > 1:
        legs.append(("smoke-sharded", True))
    for variant_id, sharded in legs:
        variant_path = os.path.join(scratch, f"{variant_id}.json")
        write_variant(variant_path, variant_id, ratings, sharded)
        t0 = time.perf_counter()
        instance_id = run_train(
            children, env, variant_path,
            os.path.join(scratch, f"train-{variant_id}.log"),
        )
        wall = time.perf_counter() - t0
        inst = storage.get_meta_data_engine_instances().get(instance_id)
        stages = json.loads(inst.env["stage_timings"])
        profile = json.loads(inst.env["device_profile"])
        train_row = check_train_profile(profile, probe, not rehearsal)
        compile_s = sum(
            e["compile_seconds"] for e in profile["executables"].values()
        )
        phase(f"train[{variant_id}] process", wall,
              "(start to exit, one child)")
        phase(f"train[{variant_id}] read", stages["read"],
              "(ratings file -> COO arrays)")
        phase(f"train[{variant_id}] train stage", stages["train"],
              f"(host prep + staging + compile + {ITERATIONS} iterations "
              f"of {train_row['name']} + factor fetch)")
        # one compile-paying call cannot split device time from compile
        # honestly, so only the compile share is broken out
        phase(f"train[{variant_id}]   of which compile", compile_s,
              "(trace + lower + backend compile or cache fetch, all "
              "executables)")
        phase(f"train[{variant_id}] persist", stages["persist"])

        t0 = time.perf_counter()
        export = os.path.join(scratch, f"{variant_id}.npz")
        proc = children.start(
            [sys.executable, "-c", _EXPORT, instance_id, export],
            dict(env, JAX_PLATFORMS="cpu"),
            os.path.join(scratch, f"export-{variant_id}.log"),
        )
        if proc.wait(timeout=300) != 0:
            raise SmokeFailure(
                "model export failed:\n"
                + tail(os.path.join(scratch, f"export-{variant_id}.log"))
            )
        with np.load(export) as z:
            model = {k: z[k] for k in z.files}
        want_shape = (
            (size["n_users"], RANK), (size["n_items"], RANK)
        )
        if (model["uf"].shape, model["itf"].shape) != want_shape:
            raise SmokeFailure(
                f"persisted factors are {model['uf'].shape} / "
                f"{model['itf'].shape}, expected {want_shape}"
            )
        model["u_row"] = dict(
            zip(model["user_ids"].tolist(), model["user_rows"].tolist())
        )
        model["i_row"] = dict(
            zip(model["item_ids"].tolist(), model["item_rows"].tolist())
        )
        check_factors(model, rows, cols, SEED)
        phase(f"factors[{variant_id}]", time.perf_counter() - t0)

        serve_leg(
            children, env, variant_path,
            os.path.join(scratch, f"deploy-{variant_id}.log"),
            model, probe,
            expect_mode="interpret" if rehearsal else "tpu",
            sharded=sharded, seed=SEED,
        )
    return probe


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="tiny CPU walk of the control flow; never prints the result",
    )
    ap.add_argument(
        "--devices", type=int, default=1,
        help="virtual CPU devices for --rehearsal (>1 rehearses the "
             "sharded leg)",
    )
    ap.add_argument(
        "--keep", action="store_true", help="keep the scratch directory",
    )
    args = ap.parse_args()
    if args.devices != 1 and not args.rehearsal:
        ap.error("--devices only applies to --rehearsal")
    if not os.path.isdir(os.path.join(ROOT, "predictionio_tpu")):
        print("chip_smoke: FAILED: predictionio_tpu/ is not beside this "
              "script — run it from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)

    def on_term(signum, _frame):
        raise KeyboardInterrupt(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_term)
    children = Children()
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.perf_counter()
    try:
        if args.rehearsal:
            print("[smoke] rehearsal: tiny shapes on the CPU, Pallas in "
                  "interpret mode — not a chip run, no result line",
                  flush=True)
        device = run(args, children, scratch)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        children.stop_all()
        if args.keep:
            print(f"[smoke] scratch kept at {scratch}", flush=True)
        else:
            shutil.rmtree(scratch, ignore_errors=True)
    phase("total", time.perf_counter() - t0)
    if args.rehearsal:
        print("[smoke] rehearsal complete", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
