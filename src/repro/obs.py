"""Spans and program-build counts of the mapping and serving path.

Spans go through the JAX profiler (`jax.profiler.TraceAnnotation`), so
they land on the same clock as the device trace while one is being
taken; the profiler keeps them in memory and writes them when the trace
stops. While no trace is being taken, `span` costs one C call and
returns a shared no-op. Every span name starts with `rapidx.`; a span
whose thread is blocked rather than working carries the stat `wait=1`.
Call sites compute a stat that costs more than a field read only inside
`if enabled():`.

The build counter listens to JAX's backend-compile event, which fires
for every program the process builds, whether XLA compiles it or loads
it from the persistent compilation cache. It counts programs and
seconds per `fun_name` for the whole process (`programs_built()`), and
per thread for `build_span`, which records the programs its own thread
built inside it as the stat `builds`.
"""

from __future__ import annotations

import threading

import jax

PREFIX = "rapidx."
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

#: True while a profiler trace is being taken.
enabled = jax.profiler.TraceAnnotation.is_enabled


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats) -> None:
        pass


NO_SPAN = _NoSpan()


def span(name: str, **stats):
    """A profiler span `rapidx.<name>` with `stats`, or the shared no-op
    while no trace is being taken. `set_metadata(**stats)` on what it
    returns adds stats before the span ends."""
    if not enabled():
        return NO_SPAN
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


class _Builds:
    """Programs built, per `fun_name` for the process and per thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self._by_fun: dict[str, list] = {}
        self._thread = threading.local()
        self._installed = False

    def install(self) -> None:
        with self._lock:
            if self._installed:
                return
            self._installed = True
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event != _BACKEND_COMPILE:
            return
        with self._lock:
            entry = self._by_fun.setdefault(kwargs.get("fun_name", "?"),
                                            [0, 0.0])
            entry[0] += 1
            entry[1] += duration
        self._thread.count = self.on_this_thread() + 1

    def on_this_thread(self) -> int:
        return getattr(self._thread, "count", 0)

    def snapshot(self) -> dict:
        with self._lock:
            return {name: {"count": c, "seconds": s}
                    for name, (c, s) in self._by_fun.items()}


_BUILDS = _Builds()


def install() -> None:
    """Start counting program builds (once per process; later calls do
    nothing). The engine calls it when it is constructed."""
    _BUILDS.install()


def programs_built() -> dict:
    """{fun_name: {"count", "seconds"}} of every program built since
    `install()`: a copy."""
    return _BUILDS.snapshot()


def describe(built: dict) -> str:
    """One line for operators: `fun_name count (seconds s)`, most built
    first, of a `programs_built()` dict (or a difference of two)."""
    rows = sorted(built.items(), key=lambda kv: (-kv[1]["count"], kv[0]))
    return ", ".join(f"{fun} {b['count']} ({b['seconds']:.3f} s)"
                     for fun, b in rows if b["count"]) or "none"


class _BuildSpan(jax.profiler.TraceAnnotation):
    def __enter__(self):
        self._before = _BUILDS.on_this_thread()
        return super().__enter__()

    def __exit__(self, *exc):
        self.set_metadata(builds=_BUILDS.on_this_thread() - self._before)
        return super().__exit__(*exc)


def build_span(name: str, **stats):
    """`span`, which also records as the stat `builds` the programs its
    thread built while it was open."""
    if not enabled():
        return NO_SPAN
    return _BuildSpan(PREFIX + name, **stats)


__all__ = ["enabled", "span", "build_span", "install", "programs_built",
           "describe", "NO_SPAN", "PREFIX"]
