"""`repro.obs` — serving telemetry (DESIGN.md §12).

One ``Telemetry`` handle threads through the serving stack
(``Engine(..., telemetry=...)``) and owns the three observability
surfaces:

  - a ``MetricsRegistry`` of counters / gauges / histograms
    (repro.obs.metrics).  The engine's core run counters live here
    unconditionally — they replaced equally-cheap attribute increments
    and ``Engine.run``'s stats are diffs of them;
  - per-step **phase timers** and per-request **lifecycle spans**
    recorded into a ``TraceBuffer`` (repro.obs.trace), exported as a
    Chrome-trace/Perfetto JSON.  Each phase also opens a
    ``jax.profiler.TraceAnnotation`` named ``serve.<phase>`` (stats
    ``step`` and ``replica``), so a profiler trace holds the engine's
    phases on the same clock as the device's operations;
  - per-step **pool gauges** (allocator occupancy, prefix hit rate)
    recorded both as registry gauges and as trace counter samples.

The disabled path (``enabled=False``, the engine default) is a no-op:
``phase()`` returns one shared null context manager, ``event()`` and
``sample()`` return after a single attribute check, no clock is read
and no profiler annotation is made.  Instrumentation is host-side only
by construction — nothing in this package may touch a jitted function,
a device array, or the engine's RNG, which is why metrics-on and
metrics-off engine outputs are byte-identical (tests/test_obs.py).

``compile_log()`` is the one process-wide ``jax.monitoring`` listener
(``CompileLog``) that the engine reads at each step for ``serve/compiles``
and, with telemetry on, the ``compile`` phase.
"""
from __future__ import annotations

import threading
import time

from jax import monitoring
from jax.profiler import TraceAnnotation

from repro.obs.export import (json_snapshot, prometheus_text,
                              write_snapshot)
from repro.obs.metrics import (DEFAULT_TIME_BUCKETS, Counter, Gauge,
                               Histogram, MetricsRegistry)
from repro.obs.trace import TraceBuffer, to_chrome, write_chrome


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_CTX = _NullCtx()


class _PhaseTimer:
    """Times one engine-step phase: histogram observe + trace event, inside
    a profiler annotation ``serve.<phase>`` that encloses both clock reads."""

    __slots__ = ("tel", "name", "step", "t0", "ann")

    def __init__(self, tel: "Telemetry", name: str, step: int):
        self.tel = tel
        self.name = name
        self.step = step

    def __enter__(self):
        self.ann = TraceAnnotation("serve." + self.name, step=self.step,
                                   replica=self.tel.track)
        self.ann.__enter__()
        self.t0 = self.tel.trace.now()
        return self

    def __exit__(self, *exc):
        t1 = self.tel.trace.now()
        self.ann.__exit__(*exc)
        self.tel.registry.histogram("phase/" + self.name).observe(
            t1 - self.t0)
        self.tel.trace.add_phase(self.step, self.name, self.t0, t1,
                                 track=self.tel.track)
        return False


_COMPILE = "/jax/core/compile/"
_TRACE = _COMPILE + "jaxpr_trace_duration"


class CompileLog:
    """Process-wide tally of the programs JAX traced and of the seconds it
    spent tracing, lowering and compiling (``jax.monitoring`` events
    ``/jax/core/compile/*_duration``).  A trace nested inside another
    trace (a jitted library function called by the program being traced)
    belongs to that program: it is counted once, and its seconds once, so
    the seconds of one thread never overlap."""

    def __init__(self):
        self._lock = threading.Lock()
        self._depth = threading.local()    # open traces on this thread
        self.programs = 0
        self.seconds = 0.0

    def _enter(self, event: str, value, **kw) -> None:
        if event == _TRACE:
            self._depth.n = getattr(self._depth, "n", 0) + 1

    def _exit(self, event: str, secs: float, **kw) -> None:
        if not event.startswith(_COMPILE):
            return
        if event == _TRACE:
            self._depth.n = max(getattr(self._depth, "n", 0) - 1, 0)
        if getattr(self._depth, "n", 0):
            return              # inside a trace: its seconds hold these
        with self._lock:
            if event == _TRACE:
                self.programs += 1
            self.seconds += secs

    def totals(self) -> tuple[int, float]:
        """(programs traced, compile seconds) since the log was made."""
        with self._lock:
            return self.programs, self.seconds


_compile_log: CompileLog | None = None
_compile_lock = threading.Lock()


def compile_log() -> CompileLog:
    """The process's one ``CompileLog``, registered with
    ``jax.monitoring`` on first use."""
    global _compile_log
    with _compile_lock:
        if _compile_log is None:
            log = CompileLog()
            monitoring.register_scalar_listener(log._enter)
            monitoring.register_event_duration_secs_listener(log._exit)
            _compile_log = log
        return _compile_log


class Telemetry:
    """One observability handle.

    ``trace``/``track`` support replicated serving: a cluster builds one
    shared :class:`TraceBuffer` and hands each replica its own Telemetry
    view (``Telemetry(trace=shared, track=i)``) — phases from every
    replica land in one Chrome trace on separate tracks, while each view
    keeps a *private* MetricsRegistry (an engine's ``reset()``/restore
    rewrites its counters, which must not clobber cluster totals)."""

    def __init__(self, enabled: bool = True, clock=time.perf_counter,
                 trace: TraceBuffer | None = None, track: int = 0):
        self.enabled = enabled
        self.registry = MetricsRegistry()
        self.trace = trace if trace is not None else TraceBuffer(clock=clock)
        self.track = track

    def phase(self, name: str, step: int = 0):
        """Context manager timing one step phase; null when disabled."""
        if not self.enabled:
            return NULL_CTX
        return _PhaseTimer(self, name, step)

    def event(self, kind: str, rid: int, **meta) -> None:
        """One request-lifecycle point (submit/admit/first_chunk/
        first_token/preempt/resume/finish).  ``meta`` rides on the trace
        span — finish events carry their terminal ``reason`` so traces
        distinguish shed / deadline / cancelled / completed."""
        if not self.enabled:
            return
        self.trace.add_span(rid, kind, **meta)
        self.registry.counter("lifecycle/" + kind).inc()

    def sample(self, name: str, values: dict[str, float]) -> None:
        """One gauge-group sample: registry gauges + a trace counter
        event (Perfetto draws these as occupancy-over-time charts)."""
        if not self.enabled:
            return
        for k, v in values.items():
            self.registry.gauge(f"{name}/{k}").set(v)
        self.trace.add_counter(name, values)

    def record_phase(self, name: str, seconds: float, step: int = 0) -> None:
        """A phase timed elsewhere (compiles, from ``CompileLog``):
        histogram ``phase/<name>`` plus a trace event ending now."""
        if not self.enabled:
            return
        t1 = self.trace.now()
        self.registry.histogram("phase/" + name).observe(seconds)
        self.trace.add_phase(step, name, t1 - seconds, t1, track=self.track)

    def observe(self, name: str, value: float,
                buckets: tuple[float, ...] = ()) -> None:
        """Histogram observe, gated (use for optional distributions —
        spec acceptance, TTFT — not for the always-on run counters)."""
        if not self.enabled:
            return
        self.registry.histogram(name, buckets).observe(value)


__all__ = ["Telemetry", "compile_log", "MetricsRegistry", "Counter", "Gauge",
           "Histogram", "TraceBuffer", "DEFAULT_TIME_BUCKETS", "NULL_CTX",
           "to_chrome", "write_chrome", "prometheus_text", "json_snapshot",
           "write_snapshot"]
