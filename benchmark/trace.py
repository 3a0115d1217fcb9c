"""From a profiler trace to device numbers.

Two steps, so that the arithmetic can be checked on a small recorded
trace (``benchmark/tests/data/``): ``extract`` reads the profiler's
``.xplane.pb`` into plain lists, keeping the device planes' operation and
program lines and the harness's own host annotations; ``reduce`` turns
those lists into busy seconds, per-program device seconds and a
breakdown.

Times are nanoseconds on the profiler's clock, the same for host and
device planes.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Optional

#: device planes of a TPU trace, and the lines read from them
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"
#: a program's event carries its fingerprint: "jit_step(1234567890)"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def extract(xplane_path: str, annotations: tuple) -> dict:
    """``{"devices": [{"plane", "ops": [[name, start, dur]...],
    "programs": [...]}], "annotations": [[name, start, dur]...]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, notes = [], []
    wanted = set(annotations)
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"plane": plane.name, "ops": [], "programs": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dest = dev["ops"]
                elif line.name == PROGRAMS_LINE:
                    dest = dev["programs"]
                else:
                    continue
                for ev in line.events:
                    dest.append([ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)])
            devices.append(dev)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        notes.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    notes.sort(key=lambda e: e[1])
    return {"devices": devices, "annotations": notes}


def outline(xplane_path: str, top: int = 12) -> list:
    """Planes, lines, event counts and the most frequent names of a trace:
    what to look at by hand before trusting a reduction."""
    from collections import Counter
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        for line in plane.lines:
            names = Counter()
            n, lo, hi = 0, None, None
            for ev in line.events:
                n += 1
                names[ev.name] += 1
                lo = ev.start_ns if lo is None else min(lo, ev.start_ns)
                end = ev.start_ns + ev.duration_ns
                hi = end if hi is None else max(hi, end)
            out.append({"plane": plane.name, "line": line.name, "events": n,
                        "first_ns": lo, "last_ns": hi,
                        "names": names.most_common(top)})
    return out


def union_ns(intervals: list) -> tuple:
    """(total ns covered, merged [start, end] list) of ``[start, end]``
    intervals."""
    merged: list = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), merged


def _clip(events: list, lo: int, hi: int) -> list:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def program_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


_HLO = re.compile(r"^(%[\w.\-]+) = (.*?)\s([a-z][a-z0-9\-]*)\(")


def op_name(event_name: str) -> str:
    """An operation's event is named by its whole HLO line; keep the
    instruction's name, its kind and the start of its result shape:
    ``%while.187 while (u32[], u32[], u32[1,8388608]...``."""
    m = _HLO.match(event_name)
    if not m:
        return event_name[:96]
    name, shape, kind = m.groups()
    return f"{name} {kind} {shape[:56]}"


def reduce(trace: dict, top: int = 10) -> Optional[dict]:
    """The traced window is the span of the harness's annotations (first
    start to last end), or of the device events where there are none.

    Returns None where the trace has no device plane with an operation in
    it (a CPU rehearsal), else::

        {"window_s", "busy_s" (union of operation intervals, averaged over
         the device planes), "devices": n,
         "program_s": {program: device seconds, summed over planes / n},
         "program_runs": {program: count on the first plane},
         "device_ops": [[name, seconds], ...] top by time,
         "idle_gaps": [[label, seconds], ...] top by time}

    An idle gap is labelled by the annotation it falls in and the program
    that ran before it: ``"tick after jit_epoch"``."""
    devices = [d for d in trace["devices"] if d["ops"]]
    if not devices:
        return None
    notes = trace["annotations"]
    if notes:
        lo = min(s for _n, s, _d in notes)
        hi = max(s + d for _n, s, d in notes)
    else:
        lo = min(s for d in devices for _n, s, _d in d["ops"])
        hi = max(s + du for d in devices for _n, s, du in d["ops"])
    if hi <= lo:
        return None
    n = len(devices)
    busy_total = 0
    program_s: dict = {}
    op_s: dict = {}
    for dev in devices:
        ops = _clip(dev["ops"], lo, hi)
        covered, _ = union_ns([[s, e] for _n, s, e in ops])
        busy_total += covered
        for name, s, e in ops:
            key = op_name(name)
            op_s[key] = op_s.get(key, 0) + (e - s)
        for name, s, e in _clip(dev["programs"], lo, hi):
            key = program_name(name)
            program_s[key] = program_s.get(key, 0) + (e - s)
    first = devices[0]
    runs: dict = {}
    for name, _s, _e in _clip(first["programs"], lo, hi):
        key = program_name(name)
        runs[key] = runs.get(key, 0) + 1
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "devices": n,
        "program_s": {k: v / n / 1e9 for k, v in program_s.items()},
        "program_runs": runs,
        "device_ops": _top({k: v / n / 1e9 for k, v in op_s.items()}, top),
        "idle_gaps": _top(_idle_gaps(first, notes, lo, hi), top),
    }


def _top(seconds_by_name: dict, top: int) -> list:
    ranked = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])
    return [[name, secs] for name, secs in ranked[:top]]


def _idle_gaps(device: dict, notes: list, lo: int, hi: int) -> dict:
    """Seconds of idle device time by label, on one device plane."""
    _, busy = union_ns([[s, e] for _n, s, e in _clip(device["ops"], lo, hi)])
    programs = sorted(_clip(device["programs"], lo, hi), key=lambda p: p[1])
    spans = sorted(((s, s + d, name) for name, s, d in notes))
    gaps = []
    cursor = lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    out: dict = {}
    pi = 0
    before = "start"
    for g0, g1 in gaps:
        while pi < len(programs) and programs[pi][1] <= g0:
            before = program_name(programs[pi][0])
            pi += 1
        mid = (g0 + g1) // 2
        inside = next((name for s, e, name in spans if s <= mid < e),
                      "between barriers")
        label = f"{inside} after {before}"
        out[label] = out.get(label, 0) + (g1 - g0) / 1e9
    return out
