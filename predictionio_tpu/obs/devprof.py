"""Device profiling: per-executable XLA cost/memory accounting + roofline.

PR 1 metrics say how long a request took and PR 2 spans say where the
wall time went — but neither says what the DEVICE did with it. This
module closes that gap (Williams et al.'s Roofline model, CACM 2009,
applied with Dapper's always-on production posture): every top-level
jit boundary the framework dispatches (train loops in models/als.py,
the dense edge passes in ops/dense.py, the serving kernels) is wrapped
by `instrument(name, fn)`, and the process-global `DeviceProfiler`
records, per named executable:

- FLOPs / bytes-accessed from XLA's `cost_analysis()` (computed ONCE
  per compiled signature from the cheap `Lowered` handle — no second
  backend compile);
- argument/output bytes from the concrete call, plus temp/generated-
  code bytes from `memory_analysis()` for wrappers that opt into
  `memory=True` (this one DOES pay a duplicate backend compile per
  signature, so only small serving programs enable it — their extra
  ~100 ms lands in warmup, never in a live query);
- compile seconds (diffed off jaxmon's compile listener around the
  first call per signature, which also keeps the first call's compile
  time OUT of the device-seconds accumulator);
- invocation counts and cumulative `device_seconds`: HOST wall time from
  the call to its result being ready — the wrapper blocks on the output,
  which every in-repo call site consumes immediately anyway — not time
  on the device (the profiler's trace has that). It is the sum of
  `launch_seconds` (the call itself: argument transfer and dispatch, a
  first call's compile taken out) and `wait_seconds` (the block on the
  output), the two intervals a call also opens as the spans
  `device.launch` and `device.wait` with attr `program` = the wrapper's
  name (ISSUE 37): children of the span the call runs under, state
  spans (obs/spans.py) where it runs under none.

From those it derives MFU (= executed FLOPs/s over the platform peak)
and HBM %-of-roof against a per-generation peak table (env-overridable
with PIO_PEAK_FLOPS / PIO_PEAK_HBM_BPS). Loop caveat, measured on this
jax: XLA's HLO cost analysis counts `fori_loop`/`scan` bodies ONCE
regardless of trip count, so train wrappers declare
`scale_by="iterations"` and per-call FLOPs multiply by that static
kwarg — the correction is framework-owned and recorded in the report
(`flops_scaled_by`).

Padding waste: the micro-batch dispatcher calls
`record_batch_padding(real, padded, flops=...)` per device batch; the
(padded-real)/padded ratio feeds a `batch_padding_ratio` histogram and
a wasted-FLOPs counter on the process-default registry, so every
server's `/metrics` and `GET /debug/profile` can say "38% of that
batch was padding".

Degradation contract (same as obs/jaxmon.py): importing this module
never imports jax; with jax absent every wrapper is a passthrough and
`report()` returns an empty profile; cost_analysis/memory_analysis
raising (private-API drift) zeroes that executable's analysis but
still counts invocations/seconds — serving must never 500 because
profiling broke. Set PIO_DEVPROF=0 to disable instrumentation wholesale.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional

from predictionio_tpu.obs import jaxmon as _jaxmon
from predictionio_tpu.obs import spans as _spans
from predictionio_tpu.obs.registry import MetricsRegistry, get_default_registry
from predictionio_tpu.utils.env import env_bool, env_opt_float, env_raw
from predictionio_tpu.analysis import tsan as _tsan

# -- platform peaks ---------------------------------------------------------

#: device_kind substring (lowercase) → (peak FLOP/s, peak HBM bytes/s):
#: the published per-chip bf16 dense peaks. Longest match wins ("tpu v5
#: lite" before "tpu v5"). A device_kind with no row has NO peaks — MFU
#: and the HBM fraction then read None rather than a made-up number.
PEAK_TABLE: dict[str, tuple[float, float]] = {
    "tpu v2": (45e12, 700e9),
    "tpu v3": (123e12, 900e9),
    "tpu v4": (275e12, 1228e9),
    "tpu v5 lite": (197e12, 819e9),
    "tpu v5e": (197e12, 819e9),
    "tpu v5p": (459e12, 2765e9),
    "tpu v5": (459e12, 2765e9),
    "tpu v6 lite": (918e12, 1640e9),
    "tpu v6e": (918e12, 1640e9),
}

#: dtype-aware peak FLOP/s per generation (ISSUE 11 satellite, carried
#: PR-3 follow-up): an int8 serving kernel rooflined against the bf16
#: peak under-reports how far from the hardware ceiling it really is —
#: and vice versa for f32. int8 entries are the published int8 TOPS
#: where the generation has an int8 MXU mode (v5e onward; v2–v4 run
#: int8 through the bf16 path, so int8 == bf16 there); f32 entries are
#: the bf16/2 convention of the MXU's f32 passthrough. The default
#: (no-dtype) lookup stays the bf16 column, so every pre-existing
#: number keeps its meaning. Env overrides: PIO_PEAK_FLOPS (bf16 /
#: default), PIO_PEAK_FLOPS_INT8, PIO_PEAK_FLOPS_F32.
PEAK_DTYPE_TABLE: dict[str, dict[str, float]] = {
    "tpu v2": {"f32": 22.5e12, "int8": 45e12},
    "tpu v3": {"f32": 61.5e12, "int8": 123e12},
    "tpu v4": {"f32": 137.5e12, "int8": 275e12},
    "tpu v5 lite": {"f32": 98.5e12, "int8": 394e12},
    "tpu v5e": {"f32": 98.5e12, "int8": 394e12},
    "tpu v5p": {"f32": 229.5e12, "int8": 918e12},
    "tpu v5": {"f32": 229.5e12, "int8": 918e12},
    "tpu v6 lite": {"f32": 459e12, "int8": 1836e12},
    "tpu v6e": {"f32": 459e12, "int8": 1836e12},
}

#: batch padding ratio lives in [0, 1); these resolve the interesting
#: shapes (exact fills at 0, the pow2-bucket half/quarter fills, tails)
PADDING_RATIO_BUCKETS: tuple[float, ...] = (
    0.0, 0.0625, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 0.984375,
)


def _env_float(name: str) -> Optional[float]:
    return env_opt_float(name)


def platform_info(dtype: Optional[str] = None) -> dict:
    """Platform + resolved peaks. Never imports jax: a data-plane process
    that hasn't paid the jax import reports platform None (and env
    overrides still apply, so a fleet can pin peaks centrally).

    `dtype` ("int8" | "f32" | "bf16" | None) selects the peak-FLOPs
    column (ISSUE 11 satellite); None/"bf16" keeps the legacy bf16
    entry. The resolved dtype peak rides in `peak_flops`; `peak_flops`
    with no dtype is unchanged from every prior PR."""
    platform = kind = count = None
    if "jax" in sys.modules:
        import jax

        devs = jax.devices()
        platform, kind, count = (
            devs[0].platform, devs[0].device_kind, len(devs)
        )
    dt = dtype if dtype in ("int8", "f32") else None
    env_name = {
        "int8": "PIO_PEAK_FLOPS_INT8", "f32": "PIO_PEAK_FLOPS_F32",
    }.get(dt, "PIO_PEAK_FLOPS")
    peak_flops = _env_float(env_name)
    if peak_flops is None and dt is not None:
        # a fleet pinning only PIO_PEAK_FLOPS pins every dtype: a
        # central override beats a table guess for the wrong column
        peak_flops = _env_float("PIO_PEAK_FLOPS")
    peak_hbm = _env_float("PIO_PEAK_HBM_BPS")
    source = "env" if (peak_flops or peak_hbm) else None
    if (peak_flops is None or peak_hbm is None) and kind:
        lowered = str(kind).lower()
        best = max(
            (e for e in PEAK_TABLE if e in lowered), key=len, default=None
        )
        if best is not None:
            source = source or "table"
            if peak_flops is None:
                peak_flops = PEAK_DTYPE_TABLE[best].get(
                    dt, PEAK_TABLE[best][0]
                )
            if peak_hbm is None:
                peak_hbm = PEAK_TABLE[best][1]
    return {
        "platform": platform,
        "device_kind": kind,
        "device_count": count,
        "peak_flops": peak_flops,
        "peak_hbm_bps": peak_hbm,
        "peak_source": source or "none",
        **({"peak_dtype": dt} if dt is not None else {}),
    }


def mfu(flops: float, seconds: float,
        dtype: Optional[str] = None) -> Optional[float]:
    """Executed-FLOPs utilization vs the platform peak for `dtype`
    (default bf16), clamped to 1.0 (cost-analysis estimates can
    overshoot on fused programs); None when either input or the peak is
    unknown."""
    peak = platform_info(dtype)["peak_flops"]
    if not peak or seconds <= 0 or flops <= 0:
        return None
    return min(1.0, flops / seconds / peak)


def hbm_fraction(nbytes: float, seconds: float) -> Optional[float]:
    """HBM-traffic fraction of the platform roof (same contract as mfu)."""
    peak = platform_info()["peak_hbm_bps"]
    if not peak or seconds <= 0 or nbytes <= 0:
        return None
    return min(1.0, nbytes / seconds / peak)


# -- per-executable accounting ---------------------------------------------


@dataclass
class _SigAnalysis:
    """What XLA said about one compiled signature of an executable."""

    flops: float = 0.0
    bytes_accessed: float = 0.0
    arg_bytes: float = 0.0
    output_bytes: float = 0.0
    temp_bytes: float = 0.0
    code_bytes: float = 0.0
    cost_ok: bool = False
    memory_ok: bool = False
    # loop-FLOPs calibration (ISSUE 8 satellite, the PR-3 follow-up):
    # True when `flops`/`bytes_accessed` already include the loop trip
    # count via the 1-vs-2-iteration lowering diff — the caller must
    # NOT also multiply by the `scale_by` kwarg
    calibrated: bool = False
    # the raw one-pass numbers XLA reported for the actual kwargs, kept
    # so the report can show the kwarg-scaled estimate for comparison
    flops_body: float = 0.0
    bytes_body: float = 0.0
    iterations: float = 1.0
    # per-shard attribution (ISSUE 10): the device count this
    # signature's arguments span (sharded fleet programs > 1). For the
    # shard_map programs this tree shards with, XLA lowers — and cost/
    # memory-analyzes — the PER-DEVICE module, so flops/bytes here are
    # already one shard's share; `devices` is the context a reader
    # needs to reconstruct the global program (flops × devices).
    devices: float = 1.0
    # compute dtype of this signature (ISSUE 11 satellite): set by the
    # wrapper's dtype_of hook (e.g. the serving jit reports "int8" for
    # quantized signatures); None keeps the legacy bf16 roofline
    dtype: Optional[str] = None


@dataclass
class _Exec:
    name: str
    scale_by: Optional[str] = None
    signatures: dict = field(default_factory=dict)  # sig key → _SigAnalysis
    invocations: int = 0
    # host wall time, call to ready = launch_seconds + wait_seconds
    device_seconds: float = 0.0
    launch_seconds: float = 0.0
    wait_seconds: float = 0.0
    compile_seconds: float = 0.0
    flops_total: float = 0.0
    bytes_total: float = 0.0
    # per-dtype accumulation (ISSUE 14 satellite, carried devprof
    # follow-up): a MIXED-dtype executable (fused int8+f32 serving
    # verbs, a model serving f32 while its canary serves int8) used to
    # roofline everything against its LATEST signature's peak column —
    # dtype → [flops, device_seconds, invocations] splits it so each
    # column rooflines against its own peak
    dtype_totals: dict = field(default_factory=dict)
    # signature key of the most recent call — whose static kwargs the
    # report shows as "what this executable last ran with"
    last_sig: Optional[tuple] = None


class ProfTotals(NamedTuple):
    """Cumulative device accounting — DASE stage spans diff this across
    a stage (the compile_snapshot pattern)."""

    flops: float
    bytes: float
    device_seconds: float
    invocations: int


def _leaf_sig(obj: Any) -> Any:
    shape = getattr(obj, "shape", None)
    dtype = getattr(obj, "dtype", None)
    if shape is not None and dtype is not None:
        # the dtype object itself: it hashes and compares as its name
        # does, and str() of one costs as much as the rest of a call's
        # signature (the key is built on every profiled call)
        return ("arr", tuple(shape), dtype)
    if isinstance(obj, float):
        # traced python-float scalars (λ, α sweeps) share one executable;
        # keying on the value would mint a spurious "signature" per sweep
        # point. Static floats (rare) just reuse the first analysis.
        return ("f",)
    try:
        hash(obj)
        return ("v", obj)
    except TypeError:
        return ("t", type(obj).__name__)


def _signature(args: tuple, kwargs: dict) -> tuple:
    def walk(x: Any) -> Any:
        if isinstance(x, (tuple, list)):
            return tuple(walk(v) for v in x)
        if isinstance(x, dict):
            return tuple(sorted((k, walk(v)) for k, v in x.items()))
        return _leaf_sig(x)

    return (walk(args), walk(kwargs))


def _static_kwargs(sig: Optional[tuple]) -> dict:
    """JSON-able view of a `_signature` key's hashable kwargs."""
    if sig is None or len(sig) != 2:
        return {}
    return {
        k: v[1] if isinstance(v[1], (str, int, bool, type(None)))
        else str(v[1])
        for k, v in sig[1]
        if isinstance(v, tuple) and len(v) == 2 and v[0] == "v"
    }


def _arg_device_span(args: tuple, kwargs: dict) -> float:
    """Max device count any argument's sharding spans (1 for host
    arrays and single-device jax arrays) — the divisor for per-shard
    FLOPs/HBM attribution of sharded executables (ISSUE 10)."""
    n = 1

    def walk(x: Any) -> None:
        nonlocal n
        sh = getattr(x, "sharding", None)
        if sh is not None:
            try:
                n = max(n, len(sh.device_set))
                return
            except Exception:
                pass
        if isinstance(x, dict):
            for v in x.values():
                walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)

    walk(args)
    walk(kwargs)
    return float(n)


def _arg_nbytes(args: tuple, kwargs: dict) -> float:
    total = 0.0

    def walk(x: Any) -> None:
        nonlocal total
        if isinstance(x, (tuple, list)):
            for v in x:
                walk(v)
        elif isinstance(x, dict):
            for v in x.values():
                walk(v)
        else:
            n = getattr(x, "nbytes", None)
            if isinstance(n, (int, float)):
                total += n

    walk(args)
    walk(kwargs)
    return total


def _under_trace() -> bool:
    """True while an outer jit is tracing through the wrapper — nested
    dispatches must pass straight through (timing tracers is meaningless
    and blocking them raises)."""
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import core as _core

        return not _core.trace_state_clean()
    except Exception:
        return False


def _phase_span(name: str, program: str):
    """`device.launch` / `device.wait` of one call: a child of the span
    the call runs under; a state span (in `stats()` and on the profiler's
    plane, in no trace) where it runs under none — a warm-up, a bare
    script — so that no call roots a trace of its own."""
    if _spans.current_span_id() is not None:
        return _spans.span(name, program=program)
    return _spans.state_span(name, program=program)


#: slot reservation for a signature whose first call is still in flight —
#: exactly ONE caller runs the (possibly compile-paying) analysis; racing
#: callers account their invocation with zero flops rather than also
#: analyzing (a duplicate backend compile on the live serving path)
_ANALYSIS_PENDING = _SigAnalysis()


class DeviceProfiler:
    """Thread-safe registry of profiled executables."""

    def __init__(self):
        self._lock = threading.Lock()
        self._execs: dict[str, _Exec] = {}

    # -- recording --------------------------------------------------------
    def call(self, wrapper: "_Instrumented", args: tuple, kwargs: dict):
        """Run the wrapped executable once, with best-effort accounting:
        every profiler step is fenced so a bookkeeping bug degrades to an
        unprofiled call — the wrapped function itself runs exactly once
        and its exceptions propagate untouched."""
        fn = wrapper.__wrapped__
        rec = None
        new_sig = pending_race = False
        sig: tuple = ("?",)
        t0 = s0 = 0.0
        try:
            try:
                sig = _signature(args, kwargs)
            except Exception:
                sig = ("?",)
            with self._lock:
                rec = self._execs.get(wrapper.name)
                if rec is None:
                    rec = self._execs[wrapper.name] = _Exec(
                        wrapper.name, scale_by=wrapper.scale_by
                    )
                existing = rec.signatures.get(sig)
                if existing is None:
                    # reserve the slot: racing first calls must not each
                    # run _analyze (and its optional duplicate compile)
                    rec.signatures[sig] = _ANALYSIS_PENDING
                    new_sig = True
                elif existing is _ANALYSIS_PENDING:
                    # another thread's first call is compiling this
                    # signature right now — this call will block on that
                    # compile inside jax, so its timing needs the same
                    # compile-seconds deduction a first call gets
                    pending_race = True
            if new_sig:
                # arm jax's compile listener BEFORE the compiling call so
                # the compile-seconds diff below actually sees the compile
                _jaxmon.ensure_compile_listener()
            _c0, s0 = _jaxmon.compile_snapshot()
            t0 = time.perf_counter()
        except Exception:
            rec = None
        try:
            if rec is None:
                out = fn(*args, **kwargs)
            else:
                with _phase_span("device.launch", wrapper.name):
                    out = fn(*args, **kwargs)
        except BaseException:
            # the reserved slot must not poison the signature forever —
            # a later successful call should get to analyze it
            if rec is not None and new_sig:
                with self._lock:
                    if rec.signatures.get(sig) is _ANALYSIS_PENDING:
                        del rec.signatures[sig]
            raise
        if rec is None:
            return out
        try:
            # the one more clock read that splits call-to-ready into the
            # launch and the wait for the result
            t1 = time.perf_counter()
            try:
                import jax

                with _phase_span("device.wait", wrapper.name):
                    out = jax.block_until_ready(out)
            except Exception:
                pass
            dt = time.perf_counter() - t0
            launch = t1 - t0
            compile_sec = 0.0
            analysis = None
            if new_sig or pending_race:
                _c1, s1 = _jaxmon.compile_snapshot()
                # the listener is process-global: overlapping compiles on
                # OTHER threads land in this diff too — acceptable skew,
                # bounded by how often fresh signatures race
                compile_sec = max(0.0, s1 - s0)
                # compile-paying calls (the first, and racers blocked on
                # its compile) keep trace/lower/compile time out of the
                # device-seconds accumulator so MFU reflects steady state
                dt = max(0.0, dt - compile_sec)
                # the compile ran inside the call, never in the wait
                launch = min(dt, max(0.0, launch - compile_sec))
            if new_sig:
                analysis = self._analyze(wrapper, fn, args, kwargs, out)
            scale = 1.0
            if wrapper.scale_by is not None:
                try:
                    scale = float(kwargs.get(wrapper.scale_by) or 1)
                except (TypeError, ValueError):
                    scale = 1.0
            with self._lock:
                if new_sig:
                    rec.signatures[sig] = analysis
                    rec.compile_seconds += compile_sec
                else:
                    # racing caller: the analyzer may have finished by
                    # now — use its numbers, else count flops as zero
                    analysis = rec.signatures.get(sig)
                    if analysis is None or analysis is _ANALYSIS_PENDING:
                        analysis = _ANALYSIS_PENDING
                if analysis.calibrated:
                    # the 1-vs-2-iteration lowering already folded the
                    # trip count in — kwarg scaling would double-count
                    scale = 1.0
                rec.invocations += 1
                rec.last_sig = sig
                rec.device_seconds += dt
                rec.launch_seconds += launch
                rec.wait_seconds += dt - launch
                rec.flops_total += analysis.flops * scale
                rec.bytes_total += analysis.bytes_accessed * scale
                if analysis.dtype is not None:
                    t = rec.dtype_totals.setdefault(
                        analysis.dtype, [0.0, 0.0, 0]
                    )
                    t[0] += analysis.flops * scale
                    t[1] += dt
                    t[2] += 1
        except Exception:
            pass
        return out

    def _analyze(
        self, wrapper: "_Instrumented", fn: Any, args: tuple, kwargs: dict,
        out: Any,
    ) -> _SigAnalysis:
        """XLA's view of this signature. Everything is best-effort: the
        AOT surface (`lower`, `cost_analysis`, `memory_analysis`) is
        semi-private and has drifted across jax releases — any failure
        degrades to zeros, never to an exception."""
        res = _SigAnalysis(
            arg_bytes=_arg_nbytes(args, kwargs),
            output_bytes=_arg_nbytes((out,), {}),
        )
        try:
            res.devices = _arg_device_span(args, kwargs)
        except Exception:
            pass
        if wrapper.dtype_of is not None:
            try:
                res.dtype = wrapper.dtype_of(args, kwargs)
            except Exception:
                pass
        lower = getattr(fn, "lower", None)
        if lower is None:
            return res
        try:
            lowered = lower(*args, **kwargs)
        except Exception:
            return res
        try:
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            res.flops = float(ca.get("flops", 0.0) or 0.0)
            res.bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)
            res.cost_ok = True
        except Exception:
            pass
        if res.cost_ok and wrapper.scale_by is not None:
            self._calibrate_loop(wrapper, lower, args, kwargs, res)
        if wrapper.memory_enabled():
            try:
                compiled = lowered.compile()
                ma = compiled.memory_analysis()
                res.arg_bytes = float(ma.argument_size_in_bytes)
                res.output_bytes = float(ma.output_size_in_bytes)
                res.temp_bytes = float(ma.temp_size_in_bytes)
                res.code_bytes = float(ma.generated_code_size_in_bytes)
                res.memory_ok = True
                try:
                    # post-optimization cost analysis is the more honest
                    # number when we paid for the compile anyway
                    ca = compiled.cost_analysis()
                    if isinstance(ca, (list, tuple)):
                        ca = ca[0] if ca else {}
                    if ca.get("flops"):
                        res.flops = float(ca["flops"])
                    if ca.get("bytes accessed"):
                        res.bytes_accessed = float(ca["bytes accessed"])
                except Exception:
                    pass
            except Exception:
                pass
        return res

    @staticmethod
    def _calibrate_loop(wrapper: "_Instrumented", lower: Any, args: tuple,
                        kwargs: dict, res: _SigAnalysis) -> None:
        """Calibrate loop FLOPs with 1- and 2-iteration lowerings
        (ISSUE 8 satellite, the PR-3 follow-up). XLA's HLO cost
        analysis counts a `fori_loop`/`scan` body ONCE regardless of
        trip count; PR 3 corrected by multiplying the whole program by
        the static `scale_by` kwarg — which also scales the loop-
        INVARIANT work (setup, output gather). Lowering the same
        signature at 1 and 2 iterations separates the two:

            per_iteration = cost(2) - cost(1)
            total(n)      = cost(1) + (n - 1) * per_iteration

        Lowering is trace-only (no backend compile) and runs once per
        signature. Any failure — the kwarg not accepted, cost analysis
        drift, a non-positive diff (XLA fully unrolled or folded the
        loop, where the one-pass numbers are already honest) — falls
        back to the PR-3 kwarg scaling, recorded as `flops_scaled_by`
        with `flops_calibrated: false` in the report."""
        res.flops_body, res.bytes_body = res.flops, res.bytes_accessed
        try:
            n = float(kwargs.get(wrapper.scale_by) or 1)
        except (TypeError, ValueError):
            return
        res.iterations = n
        try:
            costs = []
            for iters in (1, 2):
                ca = lower(
                    *args, **{**kwargs, wrapper.scale_by: iters}
                ).cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                costs.append((
                    float(ca.get("flops", 0.0) or 0.0),
                    float(ca.get("bytes accessed", 0.0) or 0.0),
                ))
            (f1, b1), (f2, b2) = costs
        except Exception:
            return
        if f1 <= 0 or f2 <= f1:
            # the lowering's cost does NOT scale with the trip count
            # (XLA counted the while body once): the 1-vs-2 diff can't
            # see the loop, so the kwarg fallback is the honest scaling
            return
        res.flops = f1 + (n - 1) * (f2 - f1)
        res.bytes_accessed = max(b1, b1 + (n - 1) * (b2 - b1))
        res.calibrated = True

    def record_external(self, name: str, seconds: float,
                        invocations: int = 1) -> None:
        """Attribute externally-measured device seconds to a named
        executable (callers that own their timing, e.g. a dispatcher)."""
        with self._lock:
            rec = self._execs.get(name)
            if rec is None:
                rec = self._execs[name] = _Exec(name)
            rec.device_seconds += max(0.0, seconds)
            rec.wait_seconds += max(0.0, seconds)  # the caller's wait
            rec.invocations += invocations

    # -- reading ----------------------------------------------------------
    def snapshot(self) -> ProfTotals:
        with self._lock:
            return ProfTotals(
                flops=sum(e.flops_total for e in self._execs.values()),
                bytes=sum(e.bytes_total for e in self._execs.values()),
                device_seconds=sum(
                    e.device_seconds for e in self._execs.values()
                ),
                invocations=sum(
                    e.invocations for e in self._execs.values()
                ),
            )

    def executable_count(self) -> int:
        with self._lock:
            return len(self._execs)

    def compile_seconds_total(self) -> float:
        with self._lock:
            return sum(e.compile_seconds for e in self._execs.values())

    def executable(self, name: str) -> Optional[dict]:
        with self._lock:
            rec = self._execs.get(name)
            if rec is None:
                return None
            return self._exec_dict(rec, platform_info(), {})

    def _exec_dict(self, rec: _Exec, plat: dict,
                   dtype_peaks: Optional[dict] = None) -> dict:
        sigs = [
            s for s in rec.signatures.values()
            if s is not _ANALYSIS_PENDING
        ]
        latest = sigs[-1] if sigs else _SigAnalysis()
        out = {
            "name": rec.name,
            # the static (non-array) kwargs of the most recent call —
            # kernel mode, storage dtype, mesh: which path an executable
            # took is read from what RAN, not from what a gate was
            # expected to pick
            "static_kwargs": _static_kwargs(rec.last_sig),
            "signatures": len(rec.signatures),
            "invocations": rec.invocations,
            "compile_seconds": round(rec.compile_seconds, 4),
            "device_seconds": round(rec.device_seconds, 6),
            "launch_seconds": round(rec.launch_seconds, 6),
            "wait_seconds": round(rec.wait_seconds, 6),
            "flops_per_call": latest.flops,
            "bytes_per_call": latest.bytes_accessed,
            "flops_total": rec.flops_total,
            "bytes_total": rec.bytes_total,
            "argument_bytes": latest.arg_bytes,
            "output_bytes": latest.output_bytes,
            "temp_bytes": latest.temp_bytes,
            "generated_code_bytes": latest.code_bytes,
            "cost_analysis_ok": any(s.cost_ok for s in sigs),
            "memory_analysis_ok": any(s.memory_ok for s in sigs),
        }
        if latest.devices > 1:
            # per-shard attribution (ISSUE 10). Measured semantics (see
            # tests/test_devprof_shards.py): shard_map programs — every
            # sharded executable in this tree — LOWER THE PER-DEVICE
            # module, so cost_analysis flops/bytes (and the mfu derived
            # from them against one chip's peak) are ALREADY per-shard;
            # dividing again would under-report by devices×. Likewise
            # memory_analysis sizes are per-device with replicated
            # operands counted in full — exactly the one-chip resident
            # picture — so they pass through undivided too.
            out["devices"] = latest.devices
            if latest.memory_ok:
                out["hbm_bytes_per_shard"] = (
                    latest.arg_bytes + latest.output_bytes
                    + latest.temp_bytes
                )
        if rec.scale_by is not None:
            # kept for comparison with the calibrated numbers (ISSUE 8
            # satellite): `flops_per_call_kwarg_scaled` is what the
            # PR-3 trust-the-kwarg estimate would have claimed
            out["flops_scaled_by"] = rec.scale_by
            out["flops_calibrated"] = any(s.calibrated for s in sigs)
            if latest.calibrated:
                out["flops_per_call_kwarg_scaled"] = (
                    latest.flops_body * latest.iterations
                )
        # derived roofline fields against the caller-resolved peaks (the
        # peak table + env + jax.devices lookup is process-constant, so
        # a report resolves it ONCE, not per executable per field).
        # dtype-aware (ISSUE 11): a signature that declared a compute
        # dtype rooflines against THAT column — int8 serving kernels
        # against the int8 peak, not the bf16 one. The latest signature
        # decides the LEGACY scalar fields; mixed-dtype executables
        # additionally get per-dtype columns below (ISSUE 14).
        peak_f, peak_h = plat.get("peak_flops"), plat.get("peak_hbm_bps")

        def dtyped_peak(dt: str):
            # dtyped columns resolve once per report via the shared
            # cache, keeping the once-per-report invariant above
            cache = dtype_peaks if dtype_peaks is not None else {}
            if dt not in cache:
                cache[dt] = platform_info(dt).get("peak_flops")
            return cache[dt]

        if latest.dtype is not None:
            out["dtype"] = latest.dtype
            if latest.dtype in ("int8", "f32"):
                dt_peak = dtyped_peak(latest.dtype)
                if dt_peak:
                    peak_f = dt_peak
                    out["peak_flops_dtype"] = dt_peak
        if rec.dtype_totals:
            # per-dtype columns (ISSUE 14 satellite): every dtype this
            # executable ran at rooflines against ITS OWN peak — a
            # mixed int8+f32 verb no longer reports only the latest
            # signature's column
            cols = {}
            for dt, (fl, sec, inv) in sorted(rec.dtype_totals.items()):
                col = {
                    "flops_total": fl,
                    "device_seconds": round(sec, 6),
                    "invocations": inv,
                }
                dt_peak = (
                    dtyped_peak(dt) if dt in ("int8", "f32")
                    else plat.get("peak_flops")
                )
                if dt_peak:
                    col["peak_flops"] = dt_peak
                    if sec > 0 and fl > 0:
                        col["mfu"] = round(
                            min(1.0, fl / sec / dt_peak), 8
                        )
                cols[dt] = col
            out["dtypes"] = cols
        if peak_f and rec.device_seconds > 0 and rec.flops_total > 0:
            out["mfu"] = round(
                min(1.0, rec.flops_total / rec.device_seconds / peak_f), 8
            )
            out["flops_per_sec"] = rec.flops_total / rec.device_seconds
        if peak_h and rec.device_seconds > 0 and rec.bytes_total > 0:
            out["hbm_fraction_of_roof"] = round(
                min(1.0, rec.bytes_total / rec.device_seconds / peak_h), 8
            )
            out["hbm_bytes_per_sec"] = rec.bytes_total / rec.device_seconds
        return out

    def report(self) -> dict:
        """The `GET /debug/profile` payload: platform + peaks, every
        profiled executable with derived roofline numbers, padding-waste
        accounting, and process totals."""
        plat = platform_info()
        dtype_peaks: dict = {}  # shared per-report dtype-column cache
        with self._lock:
            rows = [
                self._exec_dict(r, plat, dtype_peaks)
                for r in self._execs.values()
            ]
        rows.sort(key=lambda r: -r["device_seconds"])
        totals = self.snapshot()
        peak_f = plat.get("peak_flops")
        report: dict[str, Any] = {
            "platform": plat,
            "executables": rows,
            "totals": {
                "flops": totals.flops,
                "bytes": totals.bytes,
                "device_seconds": round(totals.device_seconds, 6),
                "invocations": totals.invocations,
                "mfu": (
                    min(1.0, totals.flops / totals.device_seconds / peak_f)
                    if peak_f and totals.device_seconds > 0
                    and totals.flops > 0 else None
                ),
            },
            "padding": padding_summary(),
        }
        return report

    def clear(self) -> None:
        with self._lock:
            self._execs.clear()


_profiler = DeviceProfiler()


def get_profiler() -> DeviceProfiler:
    return _profiler


def snapshot() -> ProfTotals:
    """Module-level convenience — the stage-span diff pattern."""
    return _profiler.snapshot()


def report() -> dict:
    return _profiler.report()


def _enabled() -> bool:
    return env_bool("PIO_DEVPROF")


# -- the jit-boundary hook --------------------------------------------------


class _Instrumented:
    """Callable wrapper around a jit-compiled function. Transparent when
    profiling is disabled, jax is absent, or an outer jit is tracing
    through; attribute access (`.lower`, `.clear_cache`, …) forwards to
    the wrapped function so AOT users don't notice the wrapper."""

    def __init__(self, name: str, fn: Callable,
                 scale_by: Optional[str] = None,
                 memory: bool = False,
                 dtype_of: Optional[Callable] = None):
        self.name = name
        self.__wrapped__ = fn
        self.scale_by = scale_by
        self.memory = memory
        self.dtype_of = dtype_of
        self.__doc__ = getattr(fn, "__doc__", None)

    def memory_enabled(self) -> bool:
        env = (env_raw("PIO_DEVPROF_MEMORY") or "").strip()
        if env == "0":
            return False
        if env == "1":
            return True
        return self.memory

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        # sanitizer hook (ISSUE 12): a lock held across a device
        # dispatch serializes the whole server behind it — near-zero
        # cost (one bool) when PIO_TSAN is off
        _tsan.note_blocking("device.dispatch")
        if not _enabled() or "jax" not in sys.modules or _under_trace():
            return self.__wrapped__(*args, **kwargs)
        # call() fences all its own bookkeeping: the wrapped function
        # executes exactly once and its exceptions propagate untouched
        return _profiler.call(self, args, kwargs)

    def __getattr__(self, item: str) -> Any:
        return getattr(self.__wrapped__, item)


def instrument(name: str, fn: Callable, *, scale_by: Optional[str] = None,
               memory: bool = False,
               dtype_of: Optional[Callable] = None) -> Callable:
    """Hook a top-level jit boundary into the device profiler.

    `scale_by` names a STATIC kwarg whose value multiplies the analyzed
    per-call FLOPs/bytes — the fori_loop/scan correction (XLA's HLO cost
    analysis counts loop bodies once; verified on this jax).
    `memory=True` opts into full `memory_analysis()` (a duplicate
    backend compile per signature — small serving programs only).
    `dtype_of(args, kwargs)` declares a signature's COMPUTE dtype
    ("int8"/"f32"/"bf16") so the roofline uses that dtype's peak
    (ISSUE 11); None keeps the legacy bf16 denominator — only the call
    site knows whether its MXU work is int8 or merely int8-STORED, so
    this is explicit, never inferred from argument dtypes."""
    return _Instrumented(
        name, fn, scale_by=scale_by, memory=memory, dtype_of=dtype_of
    )


# -- padding-waste accounting ----------------------------------------------


def _padding_hist(reg: MetricsRegistry):
    """Single declaration point for the padding metrics: the recorder and
    the summary reader MUST resolve identical definitions (the registry
    raises on bucket drift between re-registrations)."""
    return reg.histogram(
        "batch_padding_ratio",
        "fraction of each coalesced device batch that was padding",
        buckets=PADDING_RATIO_BUCKETS,
    )


def _padding_counters(reg: MetricsRegistry):
    return (
        reg.counter(
            "batch_rows_real_total",
            "live query rows through device batches",
        ),
        reg.counter(
            "batch_rows_padded_total",
            "total rows (live + padding) through device batches",
        ),
        reg.counter(
            "batch_padding_wasted_flops_total",
            "device FLOPs spent computing padding rows",
        ),
    )


def record_batch_padding(real_rows: int, padded_rows: int,
                         flops: float = 0.0,
                         registry: Optional[MetricsRegistry] = None) -> None:
    """Account one padded device batch: `real_rows` live queries ran in a
    `padded_rows`-shaped program (serving-shape bucketing), so
    (padded-real)/padded of the work was waste. `flops` is the executed-
    FLOPs attribution for the batch (typically a devprof snapshot diff
    across the device call at the pad site — approximate under
    concurrent batches, exact in aggregate)."""
    if padded_rows <= 0:
        return
    real_rows = max(0, min(real_rows, padded_rows))
    ratio = (padded_rows - real_rows) / padded_rows
    reg = registry if registry is not None else get_default_registry()
    _padding_hist(reg).observe(ratio)
    real_c, padded_c, wasted_c = _padding_counters(reg)
    real_c.inc(real_rows)
    padded_c.inc(padded_rows)
    if flops > 0 and ratio > 0:
        wasted_c.inc(flops * ratio)


def padding_summary(registry: Optional[MetricsRegistry] = None) -> dict:
    """The padding section of `report()` — read back off the registry the
    pad sites record into, so /metrics and /debug/profile can never
    disagree."""
    reg = registry if registry is not None else get_default_registry()
    hist = _padding_hist(reg)
    real_c, padded_c, wasted_c = _padding_counters(reg)
    return {
        "batches": hist.count,
        "mean_padding_ratio": round(hist.mean, 6),
        "p50_padding_ratio": round(hist.quantile(0.5), 6),
        "rows_real": real_c.total,
        "rows_padded": padded_c.total,
        "wasted_flops": wasted_c.total,
    }


# -- /metrics gauges --------------------------------------------------------


def install_devprof_gauges(registry: MetricsRegistry) -> None:
    """Mount the profiler's cumulative totals as scrape-time callback
    gauges (idempotent per registry, same posture as install_jax_gauges)."""
    registry.gauge_callback(
        "devprof_executables",
        "distinct profiled executables in this process",
        lambda: float(_profiler.executable_count()),
    )
    registry.gauge_callback(
        "devprof_invocations_total",
        "profiled executable invocations",
        lambda: float(_profiler.snapshot().invocations),
    )
    registry.gauge_callback(
        "devprof_device_seconds_total",
        "cumulative host wall seconds, call to result ready, across "
        "profiled executables (launch + wait; not time on the device)",
        lambda: _profiler.snapshot().device_seconds,
    )
    registry.gauge_callback(
        "devprof_flops_total",
        "cumulative executed FLOPs across profiled executables",
        lambda: _profiler.snapshot().flops,
    )
    registry.gauge_callback(
        "devprof_bytes_total",
        "cumulative HBM bytes accessed across profiled executables",
        lambda: _profiler.snapshot().bytes,
    )
    registry.gauge_callback(
        "devprof_compile_seconds_total",
        "cumulative XLA compile seconds attributed to profiled executables",
        _profiler.compile_seconds_total,
    )

    def _lifetime_mfu() -> float:
        totals = _profiler.snapshot()  # one snapshot: coherent num/denom
        return mfu(totals.flops, totals.device_seconds) or 0.0

    registry.gauge_callback(
        "devprof_mfu",
        "process-lifetime model FLOPs utilization (0 when unknown)",
        _lifetime_mfu,
    )


# -- on-demand XLA profiler capture ----------------------------------------

_capture_lock = threading.Lock()


def capture_trace(directory: str, seconds: float) -> dict:
    """Open a jax.profiler trace window for `seconds` and write it under
    `directory` (inspect with tensorboard/xprof/perfetto). Raises
    RuntimeError when jax is not loaded in this process or a capture is
    already running — callers map those to 409."""
    seconds = float(seconds)
    if not 0.0 < seconds <= 60.0:
        raise ValueError("capture seconds must be in (0, 60]")
    if "jax" not in sys.modules:
        raise RuntimeError(
            "jax is not loaded in this process — nothing to capture"
        )
    if not _capture_lock.acquire(blocking=False):
        raise RuntimeError("a profiler capture is already running")
    try:
        import jax

        jax.profiler.start_trace(directory)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
    finally:
        _capture_lock.release()
    return {"dir": directory, "seconds": seconds}
