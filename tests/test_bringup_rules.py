"""The rules PR 21's chip bring-up put in place: one stable compile-cache
directory placed from outside, a smoke that refuses to pass without a
chip, warm-up failures that fail the deploy, a /stop that ends the
process, and the warm-up covering every exclusion wire form."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(**extra):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR",)
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


_CACHE_CHILD = (
    "import json, jax\n"
    "from predictionio_tpu.utils.jaxenv import ensure_compile_cache\n"
    "used = ensure_compile_cache()\n"
    "print(json.dumps({'used': used, "
    "'jax': jax.config.jax_compilation_cache_dir}))\n"
)


def _cache_child(env, cwd):
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_CHILD], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=120, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_dir_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it, the code sets no
    other path."""
    placed = str(tmp_path / "placed-cache")
    got = _cache_child(
        _child_env(JAX_COMPILATION_CACHE_DIR=placed), str(tmp_path)
    )
    assert got == {"used": placed, "jax": placed}


def test_compile_cache_dir_default_is_fixed_in_checkout(tmp_path):
    """Unset: one fixed, git-ignored path inside the checkout — the same
    across launches and working directories (never a temp name, pid or
    timestamp)."""
    want = os.path.join(REPO, ".jax_cache")
    first = _cache_child(_child_env(), str(tmp_path))
    other = tmp_path / "elsewhere"
    other.mkdir()
    second = _cache_child(_child_env(), str(other))
    assert first == second == {"used": want, "jax": want}
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=REPO
    )
    if os.path.isdir(os.path.join(REPO, ".git")):
        assert ignored.returncode == 0, ".jax_cache/ must be git-ignored"


def test_chip_smoke_refuses_without_a_chip(tmp_path):
    """No argument + no accelerator: non-zero within seconds, the missing
    chip named, no result line — even with JAX_PLATFORMS=cpu in the
    environment (the smoke pins its children to the TPU)."""
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_child_env(), cwd=str(tmp_path), capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "no accelerator" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = _child_env()
    env.pop("PYTHONPATH")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_unknown_tpu_kind_has_no_peaks(monkeypatch):
    """A device_kind the table lacks yields None peaks — no fall-through
    to the platform name, no default."""
    import jax

    from predictionio_tpu.obs import devprof

    class _Dev:
        platform = "tpu"
        device_kind = "TPU v99 hypothetical"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(), _Dev()])
    monkeypatch.delenv("PIO_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("PIO_PEAK_HBM_BPS", raising=False)
    info = devprof.platform_info()
    assert info["platform"] == "tpu" and info["device_count"] == 2
    assert info["peak_flops"] is None and info["peak_hbm_bps"] is None
    assert info["peak_source"] == "none"
    _Dev.device_kind = "TPU v5 lite"
    info = devprof.platform_info("int8")
    assert (info["peak_flops"], info["peak_hbm_bps"]) == (394e12, 819e9)
    assert info["peak_source"] == "table"


# -- deploy ------------------------------------------------------------------

VARIANT = {
    "id": "bringup",
    "engineFactory":
        "predictionio_tpu.engines.recommendation.RecommendationEngine",
    "datasource": {"params": {"app_name": "bringupapp"}},
    "algorithms": [
        {"name": "als", "params": {"rank": 4, "num_iterations": 2}}
    ],
}


@pytest.fixture()
def trained(fresh_storage):
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow.core import run_train

    app_id = fresh_storage.get_meta_data_apps().insert(
        App(id=0, name="bringupapp")
    )
    events = fresh_storage.get_events()
    events.init_app(app_id)
    rng = np.random.RandomState(0)
    events.insert_batch(
        [
            Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{rng.randint(12)}",
                properties={"rating": float(rng.randint(1, 6))},
            )
            for u in range(10) for _ in range(8)
        ],
        app_id,
    )
    return fresh_storage, run_train(fresh_storage, VARIANT)


def test_train_records_where_it_ran(trained):
    """The EngineInstance row says which device and which executables
    (with their static kwargs) the train used — beside stage_timings."""
    import jax

    _, inst = trained
    profile = json.loads(inst.env["device_profile"])
    devs = jax.devices()
    assert profile["platform"] == devs[0].platform
    assert profile["device_kind"] == devs[0].device_kind
    assert profile["device_count"] == len(devs)
    row = profile["executables"]["als.train_windowed"]
    assert row["invocations"] >= 1
    assert row["static_kwargs"]["pallas_mode"] is None  # XLA on the CPU


def test_failed_warmup_fails_the_deploy(trained, monkeypatch):
    """A warm-up that raises must raise out of build_runtime — not log
    and come up live to 500 on the first real query."""
    from predictionio_tpu.engines.recommendation.engine import ALSAlgorithm
    from predictionio_tpu.workflow.server import build_runtime

    storage, inst = trained

    def boom(self, model):
        raise RuntimeError("kernel refused to lower")

    monkeypatch.setattr(ALSAlgorithm, "warmup", boom)
    with pytest.raises(RuntimeError, match="kernel refused to lower"):
        build_runtime(storage, inst)


def test_warmup_covers_rowlist_and_packed_bit_programs(trained, monkeypatch):
    """Warm-up must run, per batch bucket, the no-mask program AND both
    exclusion wire forms — a row list and packed bit words — so the
    first filtered query does not meet an uncompiled (or, on the chip,
    an un-lowerable) program."""
    from predictionio_tpu.models import als
    from predictionio_tpu.workflow.server import build_runtime

    storage, inst = trained
    seen = []
    real = als.recommend_serving

    def spy(serving, rows, k, exclude_mask=None, exclude_rows=None):
        seen.append((
            len(rows),
            "bits" if exclude_mask is not None
            else "rows" if exclude_rows is not None else "none",
        ))
        return real(serving, rows, k, exclude_mask=exclude_mask,
                    exclude_rows=exclude_rows)

    monkeypatch.setattr(als, "recommend_serving", spy)
    build_runtime(storage, inst)
    assert set(seen) == {
        (b, kind) for b in (1, 8, 64) for kind in ("none", "rows", "bits")
    }


def test_stop_ends_the_serving_wait(trained):
    """GET /stop must end `pio deploy`'s wait: ServerProcess.wait()
    returns once the server has stopped itself."""
    import threading
    import urllib.request

    from predictionio_tpu.workflow.server import (
        QueryServer,
        QueryServerConfig,
        build_runtime,
    )

    storage, inst = trained
    srv = QueryServer(
        storage, build_runtime(storage, inst),
        QueryServerConfig(ip="127.0.0.1", port=0),
    )
    port = srv.start()
    waiter = threading.Thread(target=srv.wait)
    waiter.start()
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stop", timeout=10
        ).read()
        waiter.join(timeout=30)
        assert not waiter.is_alive(), "wait() still blocked after /stop"
    finally:
        if waiter.is_alive():
            srv.stop()
            waiter.join(timeout=10)
