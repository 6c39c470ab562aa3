"""From a profiler trace to numbers: device busy time, per-operation and
per-program device time, and the idle gaps by what the host was doing.

The jax profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`;
`jax.profiler.ProfileData` reads it with nothing but jax. `load_xplane`
turns it into plain lists (`{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, dur_ns], ...]}]}]}`), which is also the form of
the small recorded trace the tests keep, and `reduce_trace` works on that
form only, so the arithmetic is checked without a chip.

What a TPU trace looks like (looked at by hand on the v5e, PR 24): one plane
per chip, "/device:TPU:<n>", with the lines "XLA Modules" (one event per run
of a jitted program, named "jit_<function>(<fingerprint>)"), "XLA Ops" (the
HLO operations inside it; a `while` is an event that encloses its body's
events on the same line) and "Steps"; host threads are lines of the plane
"/host:CPU".
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: gaps shorter than this are launch latency between back-to-back
#: operations, not something the host did
MIN_GAP_NS = 20_000


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def dump(trace: dict, path: str, min_host_ns: int = 200_000) -> None:
    """Write the plain form, gzipped; host events under `min_host_ns` are
    dropped (there are millions), device events all stay."""
    import gzip
    import json

    slim = {"planes": []}
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = line["events"]
            if plane["name"] == HOST_PLANE:
                events = [e for e in events if e[2] >= min_host_ns]
            if events:
                lines.append({"name": line["name"], "events": events})
        slim["planes"].append({"name": plane["name"], "lines": lines})
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(slim, f)


def program_name(module_event_name: str) -> str:
    """'jit__train_jit_dense(1234567)' -> '_train_jit_dense'."""
    name = re.sub(r"\(.*\)$", "", module_event_name)
    return name[4:] if name.startswith("jit_") else name


def short_op_name(name: str) -> str:
    """'%fusion.2 = s8[464896,17920]{1,0:T(8,128)} fusion(...)' ->
    'fusion.2 s8[464896,17920] fusion': what ran, on what shape."""
    m = re.match(r"%?(\S+) = \(?(\w+\[[\d,]*\])?[^ ]* (?:.*? )?([\w\-]+)\(", name)
    if not m:
        return name[:100]
    return " ".join(x for x in (m.group(1), m.group(2), m.group(3)) if x)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _self_times(events: list[list]) -> dict[str, float]:
    """Seconds per name with each enclosing event (a `while`, a fusion
    region) charged only for what its children do not cover."""
    out: dict[str, int] = {}
    stack: list[list] = []  # [name, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0) + max(self_ns, 0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return {k: v / 1e9 for k, v in out.items()}


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over the device planes
    n_devices: int
    op_seconds: dict[str, float] = field(default_factory=dict)
    op_counts: dict[str, int] = field(default_factory=dict)
    #: program name -> device durations (s) of each of its runs, in order,
    #: on the first device
    program_runs: dict[str, list[float]] = field(default_factory=dict)
    idle_gaps: list[tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, name: str) -> float:
        return float(sum(self.program_runs.get(name, ())))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[short_op_name(k), v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]],
        }


def _host_index(planes: list[dict]):
    """Host events sorted by start, for 'what ran at instant t'."""
    events = []
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if dur > 0:
                    events.append((start, start + dur, name))
    events.sort()
    return events, [e[0] for e in events]


def _host_activity(index, t: int, max_back: int = 4096) -> str:
    """Name of the innermost host event that covers instant t."""
    events, starts = index
    i = bisect.bisect_right(starts, t) - 1
    best = None
    steps = 0
    while i >= 0 and steps < max_back:
        start, end, name = events[i]
        if (end > t and not name.startswith("$<unknown>")
                and (best is None or end - start < best[0])):
            best = (end - start, name)
        i -= 1
        steps += 1
    return best[1] if best else "(no host event)"


#: a long gap is charged to what the host did at this many instants of it
GAP_SAMPLES = 16


def reduce_trace(trace: dict, window_s: float | None = None,
                 clip: tuple[float, float] | None = None) -> TraceSummary:
    """`window_s` is the traced window by the host's clock; where it is not
    given the window is first-to-last device event. `clip` = (start, end) in
    seconds from the trace's start narrows everything to the measured part
    of the traced window (the trace's clock starts when the profiler does);
    the window is then end - start."""
    devices = [
        p for p in trace["planes"]
        if p["name"].startswith(DEVICE_PLANE_PREFIX)
    ]
    devices.sort(key=lambda p: p["name"])
    if not devices:
        raise ValueError("the trace has no device plane")
    busy_ns = []
    first_union: list[tuple[int, int]] = []
    op_seconds: dict[str, float] = {}
    op_counts: dict[str, int] = {}
    runs: dict[str, list[float]] = {}
    for n, plane in enumerate(devices):
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        ops = lines.get(OPS_LINE, [])
        mods = lines.get(MODULES_LINE, [])
        if clip is not None:
            lo, hi = int(clip[0] * 1e9), int(clip[1] * 1e9)
            ops = [[nm, max(s, lo), min(s + d, hi) - max(s, lo)]
                   for nm, s, d in ops if s < hi and s + d > lo]
            mods = [e for e in mods if lo <= e[1] and e[1] + e[2] <= hi]
        union = _union([(s, s + d) for _, s, d in ops if d > 0])
        busy_ns.append(sum(b - a for a, b in union))
        if n == 0:
            first_union = union
            op_seconds = _self_times(ops)
            for nm, _, _ in ops:
                op_counts[nm] = op_counts.get(nm, 0) + 1
            mods = sorted(mods, key=lambda e: e[1])
            for name, _start, dur in mods:
                runs.setdefault(program_name(name), []).append(dur / 1e9)
    if sum(busy_ns) == 0:
        raise ValueError("no operation ran on the device in the trace")
    if clip is not None:
        window_s = clip[1] - clip[0]
        edges = (int(clip[0] * 1e9), int(clip[1] * 1e9))
    elif window_s is None:
        window_s = (first_union[-1][1] - first_union[0][0]) / 1e9
        edges = (first_union[0][0], first_union[-1][1])
    else:
        edges = (0, int(window_s * 1e9))
    index = _host_index(trace["planes"])
    gaps: dict[str, float] = {}
    # the window's own edges count: the wait before the first operation and
    # after the last is idle time like any other
    spans = [(edges[0], edges[0])] + first_union + [(edges[1], edges[1])]
    for (_, end), (nxt, _) in zip(spans, spans[1:]):
        if nxt - end < MIN_GAP_NS:
            continue
        n = GAP_SAMPLES if nxt - end > 1_000_000 else 1
        for i in range(n):
            t = end + (nxt - end) * (2 * i + 1) // (2 * n)
            what = _host_activity(index, t) if index[0] else "(host not traced)"
            gaps[what] = gaps.get(what, 0.0) + (nxt - end) / n / 1e9
    return TraceSummary(
        window_s=float(window_s),
        busy_s=sum(busy_ns) / len(busy_ns) / 1e9,
        n_devices=len(devices),
        op_seconds=op_seconds,
        op_counts=op_counts,
        program_runs=runs,
        idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1]),
    )


def idle_pct(reading):
    """Share of the measured window in which no operation ran on the device
    (the `device.idle_pct.*` readers, one name per kind of cell)."""
    if reading.trace is None:
        return None
    return 100.0 * reading.trace.idle_share
