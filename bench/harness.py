"""What every driver shares: the run record, the measured window, compile
counting, correctness checks and device memory."""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import shutil
import sys
import threading
import time


def log(msg: str) -> None:
    """A progress line on standard error."""
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    """One number compared with its limit; the run is correct only if
    every check holds."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read about one run."""

    cell: str
    kind: str
    config: dict
    traffic: dict
    limits: dict
    device_kind: str
    setup_s: float = math.nan
    window_t0: float = math.nan        # perf_counter at the window's start
    window_s: float = math.nan
    attempted: int = 0
    failed: int = 0
    fits: list = dataclasses.field(default_factory=list)
    requests: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)
    trace: dict | None = None
    checks: list = dataclasses.field(default_factory=list)
    memory_peak_bytes: int = 0
    compiles_in_window: int = -1
    main_thread: int = dataclasses.field(default_factory=threading.get_ident)


class CompileEvents:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (copied from ``chip_smoke.py``)."""

    def __init__(self):
        import jax
        self.compiles = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_dur(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_dur)


class Harness:
    """Set-up clock, measured window, tracing and scratch space of a run."""

    def __init__(self, rec: RunRecord, *, trace: bool, workdir: str,
                 t_start: float):
        self.rec = rec
        self.trace = trace
        self.workdir = workdir
        self.t_start = t_start
        self.events = CompileEvents()
        self._tracer = None
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)

    def path(self, *parts: str) -> str:
        return os.path.join(self.workdir, *parts)

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it begins; with
        ``trace`` the profiler and the program's span tracer run inside."""
        import jax
        from repro import obs

        rec = self.rec
        rec.setup_s = time.perf_counter() - self.t_start
        c0 = self.events.compiles
        if self.trace:
            self._tracer = obs.install(obs.Tracer())
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # host spans come from obs
            jax.profiler.start_trace(self.path("trace"),
                                     profiler_options=opts)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            t1 = time.perf_counter()
            rec.window_t0, rec.window_s = t0, t1 - t0
            rec.compiles_in_window = self.events.compiles - c0
            if self.trace:
                jax.profiler.stop_trace()
                obs.uninstall()
                epoch = self._tracer._epoch
                rec.spans = [dict(e, t0=epoch + e["ts_us"] / 1e6,
                                  dur=e["dur_us"] / 1e6)
                             for e in self._tracer.events()
                             if not e.get("instant")]

    def span_events(self, lo: float, hi: float, name: str) -> list[dict]:
        """The recorded spans called ``name`` that start in [lo, hi)."""
        return [e for e in self.rec.spans
                if e["name"] == name and lo <= e["t0"] < hi]

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest device so far."""
        import jax
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())

    def close(self) -> None:
        self.events.close()
        shutil.rmtree(self.workdir, ignore_errors=True)
