"""The program's own host spans in a profiler trace.

The program names its host stages with spans `rapidx.<stage>`
(`src/repro/obs.py`). Here they are read on the device trace's clock,
inside the harness's window:

- `program_spans` lists the host spans, clipped to the window;
- `self_intervals` splits each host line (thread) into the intervals in
  which each span is the innermost one open, so a span's self time is
  its duration minus the child spans on its line that it covers;
- `stage_seconds` splits any intervals of the window by the stages the
  host was working in (a span with the stat `wait=1` is a thread
  blocked, not working); `idle_by_stage` applies it to each device's
  idle time, and the trace reduction to each long idle gap;
- `self_ms_per_read` is what the per-read stage metrics read.

Times are nanoseconds on the trace's clock unless a name says seconds.
"""

from __future__ import annotations

import trace_reduce

PREFIX = "rapidx."
#: The idle time in which no host line's innermost span was working.
UNEXPLAINED = "none"


def window_ns(profile) -> tuple[int, int]:
    """(start, end) of the harness's window span."""
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace_reduce.WINDOW_SPAN:
                    return ev.start_ns, ev.end_ns
    raise ValueError(f"no {trace_reduce.WINDOW_SPAN} span in the trace")


def program_spans(profile, window) -> list[tuple]:
    """[(host line, start, end, name, {stat: value})] of every host
    span whose name starts with `rapidx.`, clipped to `window`; host
    lines are numbered across the host planes."""
    w0, w1 = window
    out = []
    line_id = 0
    for plane in profile.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if ev.name.startswith(PREFIX) and e > s:
                    out.append((line_id, s, e, ev.name, dict(ev.stats)))
            line_id += 1
    return out


def self_intervals(spans) -> list[tuple]:
    """[(host line, start, end, name, wait)]: on each line, the
    intervals in which `name` is the innermost open span. Spans of one
    line nest (a thread opens and closes them in order)."""
    by_line: dict[int, list] = {}
    for sp in spans:
        by_line.setdefault(sp[0], []).append(sp)
    out = []
    for line, group in by_line.items():
        group.sort(key=lambda sp: (sp[1], -sp[2]))
        stack = []  # [name, wait, end, cursor]: cursor = self time start

        def close_until(t):
            while stack and stack[-1][2] <= t:
                name, wait, end, cursor = stack.pop()
                if end > cursor:
                    out.append((line, cursor, end, name, wait))
                if stack:
                    stack[-1][3] = max(stack[-1][3], end)

        for _, s, e, name, stats in group:
            close_until(s)
            if stack and s > stack[-1][3]:
                top = stack[-1]
                out.append((line, top[3], s, top[0], top[1]))
            if stack:
                stack[-1][3] = max(stack[-1][3], e)
            stack.append([name, bool(stats.get("wait")), e, s])
        close_until(float("inf"))
    return out


def self_seconds(spans) -> dict[str, float]:
    """{span name: self time in seconds, summed}."""
    out: dict[str, float] = {}
    for _, s, e, name, _ in self_intervals(spans):
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def stat_sum(spans, name: str, stat: str) -> float:
    """The sum of `stat` over the spans called `name`."""
    return float(sum(sp[4].get(stat, 0) for sp in spans if sp[3] == name))


def _idle(reduced, device: int, window) -> list[tuple]:
    busy = trace_reduce._union(
        (s, e) for d, s, e, _ in reduced["events"] if d == device)
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def stage_marks(spans) -> list[tuple]:
    """[(time, +1 or -1, short stage name)]: where each working (not
    waiting) innermost span opens and closes, for `stage_seconds`."""
    marks = []
    for _, s, e, name, wait in self_intervals(spans):
        if not wait:
            short = name[len(PREFIX):]
            marks += [(s, 1, short), (e, -1, short)]
    return marks


def stage_seconds(marks, intervals) -> dict[str, float]:
    """Seconds of the disjoint `intervals` keyed by the working stages:
    the innermost non-wait spans open on any host line at the time,
    short names joined by "+", or UNEXPLAINED when every host line was
    waiting or in no span. `marks` is what `stage_marks` gives."""
    marks = list(marks)
    for s, e in intervals:
        marks += [(s, 1, None), (e, -1, None)]
    marks.sort(key=lambda m: (m[0], m[1]))
    open_: dict[str, int] = {}
    inside = 0
    t_prev = None
    out: dict[str, float] = {}
    for t, step, name in marks:
        if inside and t_prev is not None and t > t_prev:
            key = "+".join(sorted(n for n, c in open_.items() if c))
            key = key or UNEXPLAINED
            out[key] = out.get(key, 0.0) + (t - t_prev) * 1e-9
        if name is None:
            inside += step
        else:
            open_[name] = open_.get(name, 0) + step
        t_prev = t
    return out


def idle_by_stage(reduced, spans, window) -> dict[str, float]:
    """Device idle seconds in the window, averaged over devices, keyed
    by the working stages as `stage_seconds` keys them."""
    marks = stage_marks(spans)
    devices = range(len(reduced["devices"]))
    out: dict[str, float] = {}
    for device in devices:
        for key, sec in stage_seconds(
                marks, _idle(reduced, device, window)).items():
            out[key] = out.get(key, 0.0) + sec
    return {k: v / max(len(devices), 1) for k, v in out.items()}


def idle_explained_share(reduced, spans, window) -> float | None:
    """The share of the devices' idle time in which some host line's
    innermost span was working (not waiting)."""
    by_stage = idle_by_stage(reduced, spans, window)
    total = sum(by_stage.values())
    if not total:
        return None
    return 1.0 - by_stage.get(UNEXPLAINED, 0.0) / total


def self_ms_per_read(run, name: str) -> float | None:
    """Self time of the spans called `name` in a traced run's window, in
    milliseconds per completed read; None where the run was not traced
    or no such span ran in its window."""
    if run.trace is None:
        return None
    sec = self_seconds(run.trace["spans"]).get(name)
    if sec is None:
        return None
    return sec * 1e3 / max(run.record["reads"], 1)
