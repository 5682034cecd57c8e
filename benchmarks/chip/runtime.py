"""Set-up clock, compile counters, the traced window and the memory peak."""
from __future__ import annotations

import shutil
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional

import jax

from benchmarks.chip import trace as trace_lib

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
TRACE_DIR = Path(__file__).resolve().parent / ".trace"
# the traced span of a window: the profiler stops recording a TPU's ops after
# some hundreds of thousands of events (37 s of the offline cell), so a
# traced run stops tracing after the first step that ends past this
TRACE_S = 20.0


def model_config(config_file: Dict[str, Any], family):
    """The program's ModelConfig for a configuration file's ``model``, of
    the program family that the benchmark's family file names."""
    from repro.configs.base import ModelConfig  # noqa: PLC0415

    return ModelConfig(name=config_file["name"],
                       source=config_file["source"],
                       **dict(config_file["model"],
                              family=family.PROGRAM_FAMILY))


def program_model(cfg):
    from repro.models import build_model  # noqa: PLC0415

    return build_model(cfg)


class _Window:
    summary: Optional[Dict[str, Any]] = None


class Runtime:
    """What one run measures about itself besides its metrics."""

    def __init__(self, t_start: float, devices: List[Any], trace: bool,
                 trace_dir: Path = TRACE_DIR):
        self.t_start = t_start
        self.devices = devices
        self.tracing = trace
        self.trace_dir = trace_dir
        self.phases: Dict[str, float] = {}
        self.compile_s = 0.0
        self.compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        self.setup_s: Optional[float] = None
        self.setup_compiles = 0
        self.window_compiles = 0
        self.memory_peak: Optional[int] = None
        self.traced_work: Optional[Dict[str, float]] = None
        self.traced_until: Optional[float] = None   # s from window start
        self._in_window = False
        self._work: Dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_: Any) -> None:
        if event == COMPILE_EVENT:
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, event: str, **_: Any) -> None:
        if event == CACHE_HIT:
            self.cache["hits"] += 1
        elif event == CACHE_MISS:
            self.cache["misses"] += 1

    @contextmanager
    def phase(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + (
                time.perf_counter() - t0)

    def setup_done(self) -> float:
        self.setup_s = time.perf_counter() - self.t_start
        self.setup_compiles = self.compiles
        self.setup_compile_s = self.compile_s
        return self.setup_s

    def annotate(self, name: str):
        return (jax.profiler.TraceAnnotation(name) if self._in_window
                else nullcontext())

    @contextmanager
    def window(self) -> Iterator[_Window]:
        """The measured window; with tracing on, its first ``TRACE_S``
        seconds run under the profiler, annotated ``bench.window``, and are
        reduced to a summary once the window has closed."""
        win = _Window()
        before = self.compiles
        if self.tracing:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self._t0 = time.perf_counter()
            self._in_window = True
        try:
            yield win
        finally:
            self.window_compiles = self.compiles - before
            if self.tracing:
                if self._in_window:
                    self._stop_trace()
                devices, host = trace_lib.load_xplane(
                    trace_lib.find_xplane(str(self.trace_dir)))
                win.summary = trace_lib.reduce(devices, host)
                shutil.rmtree(self.trace_dir, ignore_errors=True)

    def step_done(self, work: Dict[str, float]) -> None:
        """Called after each step, whose end syncs the host with the device:
        stops tracing once ``TRACE_S`` have passed, keeping the work done
        and the time passed so far, so that traced time, counted work and
        host time cover the same steps."""
        self._work = work
        if self._in_window and time.perf_counter() - self._t0 >= TRACE_S:
            self._stop_trace()

    def _stop_trace(self) -> None:
        # collecting the trace takes tens of seconds: what follows it in the
        # window is not read by the per-layer metrics
        self.traced_until = time.perf_counter() - self._t0
        self.traced_work = dict(self._work)
        self._span.__exit__(None, None, None)
        self._in_window = False
        jax.profiler.stop_trace()

    def read_memory(self) -> None:
        peaks = []
        for d in self.devices:
            try:
                stats = d.memory_stats() or {}
            except Exception:  # noqa: BLE001 — backends without the probe
                stats = {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak = max(peaks) if peaks else None
