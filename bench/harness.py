"""What every driver shares: the run's context, its window and its spans.

A driver builds its part of the system in set-up, warms it up, and then
runs its traffic inside `ctx.window()`. The window records when the
measurement began and ended, counts the programs built inside it (the
listener is the one `chip_smoke.py` uses), and, with `--trace 1`, holds
the profiler open over exactly that interval. Host spans that label the
device's idle gaps are written with `ctx.span(name)`.
"""

from __future__ import annotations

import contextlib
import shutil
import sys
import tempfile
import time
from concurrent.futures import Future

import simulate

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts the programs this process builds: each backend compile
    event, which JAX records both when XLA compiles a program and when
    it loads one from the persistent cache (`hits` counts the loads)."""

    def __init__(self):
        self.count = 0
        self.hits = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1
            self.seconds += duration

    def _hit(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.hits += 1

    def install(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self._hit)
        return self


class Context:
    """One run of one cell: its settings, its clock and its records."""

    def __init__(self, *, cell: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, devices,
                 t_process: float, compiles: CompileCounter):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t_process = t_process
        self.compiles = compiles
        self.t_window = None
        self.t_window_end = None
        self.compiles_in_window = 0
        self.cache_hits_in_window = 0
        self.trace_dir = None

    def rng(self, stream: str):
        return simulate.rng_for(self.seed, stream)

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """The measured interval. Set-up ends where it begins."""
        import jax
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            # Host events: the harness's spans and the runtime's own, no
            # Python function tracing (which slows the host severalfold).
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 1
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
        before, hits = self.compiles.count, self.compiles.hits
        self.t_window = time.perf_counter()
        try:
            yield self
        finally:
            self.t_window_end = time.perf_counter()
            self.compiles_in_window = self.compiles.count - before
            self.cache_hits_in_window = self.compiles.hits - hits
            if self.trace:
                jax.profiler.stop_trace()

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_process

    @property
    def window_s(self) -> float:
        return self.t_window_end - self.t_window

    def drop_trace(self) -> None:
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir = None


def submit(service, read, ref):
    """`service.submit`, or a failed future when the service refuses the
    request (closed, or its dispatcher died): a refused request is
    counted as unanswered, never lost."""
    try:
        return service.submit(read, ref)
    except RuntimeError as exc:
        fut = Future()
        fut.set_exception(exc)
        return fut


def warm_until_quiet(ctx: Context, one_pass, max_passes: int) -> int:
    """Run `one_pass(k)` until a pass compiles nothing new (the first
    pass always counts); returns the passes run. At the cap it logs
    that passes still compile and returns: the window's compile count
    then shows what was left."""
    for k in range(max_passes):
        before = ctx.compiles.count
        t0 = time.perf_counter()
        one_pass(k)
        new = ctx.compiles.count - before
        ctx.log(f"warm-up pass {k}: {new} programs built, "
                f"{time.perf_counter() - t0:.2f} s")
        if k > 0 and new == 0:
            return k + 1
    ctx.log(f"warm-up still compiling after {max_passes} passes")
    return max_passes
