"""Reduce one profiler trace (`.xplane.pb`) to what the metrics read.

The window is the host span `bench.window` that the harness opens over
the measured interval. Per device plane (`/device:TPU:<n>`) the events
of the op line are the device's work: their union inside the window is
its busy time, and their durations summed by name are the device time
per op. An op's label for matching is its name with its stats (XLA
puts the op's origin, such as `jit(decode_packed_tb)/while`, there).
The gaps between busy intervals are the device's idle time;
each is labelled with the program's working stages that held most of
it (`spans.stage_seconds`, such as `map.submit+serve.enqueue`), or,
where no program stage was working in most of it, with the harness
span (`bench.*`) that overlapped it most on the host, or `none`. The
program's own host spans (`rapidx.*`) of the window are kept for the
metrics that read them (`spans.py`).
"""

from __future__ import annotations

import json
import pathlib
import re

import spans

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
TOP = 10
#: Characters of an op's name kept in the breakdown (a TPU trace names
#: each op by its whole HLO instruction).
SHORT = 72
#: Regular expressions that find each program part's ops by name.
NAMES_FILE = pathlib.Path(__file__).resolve().parent / "trace_names.json"


def load(trace_dir: str):
    """The profile written under `trace_dir` by `jax.profiler`."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def _op_events(plane) -> list:
    """The device's op events: those of its `XLA Ops` line, or, where a
    trace names its lines otherwise, of its busiest line."""
    lines = list(plane.lines)
    for line in lines:
        if line.name == OP_LINE:
            return list(line.events)
    return max((list(line.events) for line in lines), key=len, default=[])


def _union(intervals):
    """Merge (start, end) intervals; returns the merged, sorted list."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(profile) -> dict:
    """Busy time, op time by name and labelled idle gaps of the window.

    Returns {"window_s", "devices": [{"name", "busy_s"}],
    "op_s": {op name, cut to SHORT: seconds summed over devices},
    "events": [(device index, start ns, end ns, label)] inside the
    window,
    "gaps": [(label, seconds)] longest first, at most TOP,
    "spans": the program's host spans clipped to the window, as
    `spans.program_spans` gives them}. Times are in seconds; the trace's
    are in nanoseconds."""
    window = None
    harness_spans = []
    devices = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append((plane.name, _op_events(plane)))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith("bench."):
                    harness_spans.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = window
    op_s: dict[str, float] = {}
    events = []
    out_devices = []
    gaps = []
    for index, (name, ops) in enumerate(sorted(devices)):
        inside = []
        for ev in ops:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                inside.append((s, e))
                short = ev.name[:SHORT]
                op_s[short] = op_s.get(short, 0.0) + (e - s) * 1e-9
                label = " ".join([ev.name] + [str(v) for _, v in ev.stats])
                events.append((index, s, e, label))
        busy = _union(inside)
        out_devices.append({"name": name, "busy_s": sum(
            e - s for s, e in busy) * 1e-9})
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])
    stages = spans.program_spans(profile, window)
    marks = spans.stage_marks(stages)
    labelled = [(_label(s, e, marks, harness_spans), (e - s) * 1e-9)
                for s, e in gaps[:TOP]]
    return {"window_s": (w1 - w0) * 1e-9, "devices": out_devices,
            "op_s": op_s, "events": events, "gaps": labelled,
            "spans": stages}


def _label(s: float, e: float, marks, harness_spans) -> str:
    by_stage = spans.stage_seconds(marks, [(s, e)])
    key = max(by_stage, key=by_stage.get, default=spans.UNEXPLAINED)
    if key != spans.UNEXPLAINED:
        return key
    best, label = 0.0, "none"
    for a, b, name in harness_spans:
        overlap = min(b, e) - max(a, s)
        if overlap > best:
            best, label = overlap, name
    return label


def named_seconds(reduced: dict, part: str) -> float:
    """Device seconds in which an op whose label matches a pattern that
    `trace_names.json` gives for `part` ran: the union of those ops'
    intervals per device (an op nested in another counts once), summed
    over devices. A part that matches no op of the window is an error:
    the metrics that read it are declared only for cells that run it,
    so a miss means its name changed, never that it is absent."""
    rx = [re.compile(p) for p in json.loads(NAMES_FILE.read_text())[part]]
    by_device: dict[int, list] = {}
    for index, s, e, label in reduced["events"]:
        if any(r.search(label) for r in rx):
            by_device.setdefault(index, []).append((s, e))
    if not by_device:
        raise ValueError(f"no device op of the window matches {part!r} "
                         f"in {NAMES_FILE.name}")
    return sum(e - s for iv in by_device.values() for s, e in _union(iv)
               ) * 1e-9


def top_ops(reduced: dict) -> list:
    return sorted(([n, s] for n, s in reduced["op_s"].items()),
                  key=lambda x: -x[1])[:TOP]
