"""Reduction of a profiler trace to the benchmark's device numbers.

One module, written once: busy and idle share of the device, the share
of device time in operations whose name matches a pattern, the
operations that took most time, and the longest idle gaps named by what
the host was doing in them. It works on a plain structure

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

so that a small recorded trace (JSON, `benchmark/testdata/`) and a live
`.xplane.pb` (read with `jax.profiler.ProfileData`, nothing but JAX) go
through the same code. Device planes are `/device:TPU:<n>`; their
operations are on the line `XLA Ops`. Host spans (TraceMe events such as
`PjitFunction(...)`, `DevicePut`, and the benchmark's own
`TraceAnnotation`s) are on the lines of `/host:CPU`, on the same clock.

A CPU rehearsal has no device plane. Its XLA thunks run on host threads
(`tf_XLAPjRtCpuClient/...`); the reducer then takes those lines as the
"device" so that the path runs end to end, and says so in
`device_source`. Such numbers are never reported as device metrics."""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CPU_THUNK_LINE = re.compile(r"^tf_XLA(PjRt|Tfrt)CpuClient")
# markers the CPU thread pools emit, not work
_NOT_WORK = re.compile(r"^ThreadpoolListener::")

Trace = Dict[str, list]
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> Trace:
    """Read an `.xplane.pb` into the plain structure, keeping the device
    planes and the host plane."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        lines = []
        for line in plane.lines:
            if DEVICE_PLANE.match(plane.name) \
                    and line.name != DEVICE_OP_LINE:
                continue
            events = [[op_name(ev.name), float(ev.start_ns),
                       float(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


CUSTOM_CALL_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(event_name: str) -> str:
    """The device's op line names an event by its whole HLO text
    (`%fusion.12 = bf16[...] fusion(...)`); the operation's name is what
    stands before the `=`. A custom call is named after the jax
    transformation it came from (`jvp__.1`, `transpose_jvp___.3`), which
    says nothing of what it is, so its target is appended: every Pallas
    kernel on the TPU reads `<name>@tpu_custom_call`. Host events have no
    `=` and stay as they are."""
    name, eq, rest = event_name.partition(" = ")
    target = CUSTOM_CALL_TARGET.search(rest) if eq else None
    return name.lstrip("%") + (f"@{target.group(1)}" if target else "")


# operations that only contain others: a `while` spans every operation of
# its body. They count as busy time, but not among the operations that
# took most time, nor in a pattern's share.
CONTAINER_OP = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def load_json(path: str) -> Trace:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        return json.load(fh)


def sanitize(name: str) -> str:
    """An event name made of the characters a metric name may have."""
    return re.sub(r"[^A-Za-z0-9_.-]", "_", name)[:80]


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def find_span(trace: Trace, name: str) -> Optional[Interval]:
    """The first host event called `name` (the benchmark's annotation
    around the traced window): (start_ns, end_ns)."""
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for ev_name, start, dur in line["events"]:
                if ev_name == name:
                    return (start, start + dur)
    return None


def device_op_events(trace: Trace) -> Tuple[str, Dict[str, list]]:
    """Per device, its operation events. Returns (source, {device:
    events}); source is "device" for TPU planes, "cpu_thunks" for the
    rehearsal stand-in, "none" when nothing ran."""
    per_dev = {p["name"]: [ev for line in p["lines"] for ev in line["events"]]
               for p in trace["planes"] if DEVICE_PLANE.match(p["name"])}
    per_dev = {k: v for k, v in per_dev.items() if v}
    if per_dev:
        return "device", per_dev
    thunks = [ev for p in trace["planes"] if p["name"] == HOST_PLANE
              for line in p["lines"] if CPU_THUNK_LINE.match(line["name"])
              for ev in line["events"]
              if ev[2] > 0 and not _NOT_WORK.match(ev[0])]
    return ("cpu_thunks", {"cpu": thunks}) if thunks else ("none", {})


def host_events(trace: Trace, exclude: Iterable[str] = ()) -> list:
    skip = set(exclude)
    return [ev for p in trace["planes"] if p["name"] == HOST_PLANE
            for line in p["lines"]
            if not CPU_THUNK_LINE.match(line["name"])
            for ev in line["events"]
            if ev[2] > 0 and ev[0] not in skip
            and not _NOT_WORK.match(ev[0])]


def _clip(events: list, window: Interval) -> List[Tuple[str, float, float]]:
    w0, w1 = window
    out = []
    for name, start, dur in events:
        s, e = max(start, w0), min(start + dur, w1)
        if e > s:
            out.append((name, s, e))
    return out


def reduce_trace(trace: Trace, window_name: str,
                 op_patterns: Optional[Dict[str, str]] = None,
                 top: int = 10, max_gaps: int = 400) -> Dict:
    """Everything the benchmark reads from one trace.

    `window_name` is the annotation the benchmark put around the traced
    window; without it the window is the extent of the device's events.
    `op_patterns` maps a result key to a regular expression over
    operation names. Returns busy_s / window_s / idle_share averaged over
    the devices, the worst device's idle share, op shares (matched device
    time over busy device time) and op seconds (matched time on one
    device, averaged over the devices), the top operations and the top
    idle gaps as [name, seconds] lists."""
    source, per_dev = device_op_events(trace)
    window = find_span(trace, window_name)
    if window is None and per_dev:
        evs = [ev for v in per_dev.values() for ev in v]
        window = (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))
    if window is None or not per_dev:
        return {"device_source": source, "busy_s": 0.0, "window_s": 0.0,
                "devices": 0}
    w0, w1 = window
    window_s = (w1 - w0) / 1e9

    busy_per_dev, matched, by_name = [], {}, {}
    gaps: List[Interval] = []
    for i, dev in enumerate(sorted(per_dev)):
        clipped = _clip(per_dev[dev], window)
        union = _union((s, e) for _, s, e in clipped)
        busy_per_dev.append(sum(e - s for s, e in union) / 1e9)
        for name, s, e in clipped:
            if not CONTAINER_OP.match(name):
                by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
        if i == 0:                          # gaps of the first device
            edge = w0
            for s, e in union:
                if s > edge:
                    gaps.append((edge, s))
                edge = max(edge, e)
            if w1 > edge:
                gaps.append((edge, w1))
    n_dev = len(busy_per_dev)
    op_time = sum(by_name.values())
    for key, pattern in (op_patterns or {}).items():
        rx = re.compile(pattern)
        matched[key] = sum(t for n, t in by_name.items() if rx.search(n))

    busy_s = sum(busy_per_dev) / n_dev
    result = {
        "device_source": source,
        "devices": n_dev,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "idle_share_worst": 1.0 - min(busy_per_dev) / window_s,
        # per device: ops on one device do not overlap on the op line,
        # so matched time over summed op time is a share of busy time
        "op_share": {k: (v / op_time if op_time else 0.0)
                     for k, v in matched.items()},
        "op_seconds": {k: v / n_dev for k, v in matched.items()},
        "device_ops": [[sanitize(n), t / n_dev] for n, t in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": _name_gaps(trace, gaps, window_name, top, max_gaps),
    }
    return result


def _name_gaps(trace: Trace, gaps: List[Interval], window_name: str,
               top: int, max_gaps: int) -> list:
    """Name each of the longest gaps `<window>/<host span>`: the shortest
    host span (other than the window's own annotation) that covers at
    least half of the gap, or `python` where no traced host span does. Seconds of gaps
    with one name are added up."""
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:max_gaps]
    host = host_events(trace, exclude=(window_name,))
    named: Dict[str, float] = {}
    if host:
        starts = np.array([e[1] for e in host])
        ends = starts + np.array([e[2] for e in host])
    for g0, g1 in gaps:
        label = "python"
        if host:
            overlap = np.minimum(ends, g1) - np.maximum(starts, g0)
            # the innermost span that covers at least half of the gap:
            # a thread's main loop covers every gap and names none
            covering = np.flatnonzero(overlap >= 0.5 * (g1 - g0))
            if len(covering):
                i = covering[np.argmin((ends - starts)[covering])]
                label = sanitize(host[int(i)][0])
        key = f"{sanitize(window_name)}/{label}"
        named[key] = named.get(key, 0.0) + (g1 - g0) / 1e9
    return [[k, v] for k, v in sorted(named.items(),
                                      key=lambda kv: -kv[1])[:top]]
