"""Span tracing for the streaming/partitioning stack.

One span feeds three sinks.  ``with tracer.span("engine.fold", stall=clk,
chunk=i): ...``

* always enters a ``jax.profiler.TraceAnnotation`` under the span's name,
  so a profiler session (``--jax-profile``, the benchmark's device-trace
  slices) shows the span on the host thread that ran it, beside the
  device's operations; keyword arguments become the event's stats and the
  event name stays constant;
* credits its duration to the pass's ``StallClock`` when given one
  (``stall=``), the accumulator behind every run's stage seconds;
* appends a Chrome ``trace_event`` complete event (``ph: "X"``) only when
  the tracer is enabled (``Tracer``), so one run exports straight into
  Perfetto / ``chrome://tracing`` (see ``repro.obs.export``).

Every thread gets its own lane: the engine's prefetch thread and main
thread each record where they run.  The span's measured seconds stay on
the span object (``span.seconds``) after it exits.

Disabled tracing is the ``NULL_TRACER`` singleton: its spans still reach
the profiler and the stall clock, and record nothing in memory.  With no
profiler session a span costs one annotation (about 1 us on a CPU) and two
``perf_counter`` reads.  A traced run is bit-identical to an untraced one
(tracing only *observes* the pipeline, never reorders it).

``SPANS`` names every span the engine and the partition CLI emit; the
layer prefix (``engine.``, ``artifact.``) keeps them apart from JAX's own
host events in a profiler trace.

Instrumentation points that cannot thread a tracer argument through
(e.g. halo planning called from inside ``PartitionArtifact.save``) use
the process-global active tracer::

    with use_tracer(tracer):
        ...                    # get_tracer() returns `tracer` here,
                               # including from worker threads

The active-tracer stack is deliberately process-global, not
thread-local: the engine's prefetch thread must record into the same
trace as the main thread that activated it.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "PASS_PHASES", "SPANS",
           "get_tracer", "use_tracer"]

#: ``StreamPass.phase`` of every streaming partitioner's passes.
PASS_PHASES = ("prepartition", "scoring", "hashing", "hybrid", "buffered")

#: Every span name the engine and the partition CLI emit.  The per-chunk
#: stages (``engine.read`` on the thread that reads, the rest on the main
#: thread) share a ``chunk`` argument; ``StallClock`` keys each by the
#: part after the last dot.
SPANS = ("engine.init", "engine.resume", "engine.degrees",
         "engine.clustering", "engine.mapping", "engine.setup",
         *(f"engine.pass.{p}" for p in PASS_PHASES),
         "engine.read", "engine.queue_wait", "engine.dispatch",
         "engine.device_wait", "engine.fetch", "engine.write", "engine.fold",
         "engine.checkpoint", "engine.finalize", "artifact.save")


class _Span:
    """One timed region: profiler annotation, stall credit, and (when the
    tracer is enabled) a Chrome complete event."""

    __slots__ = ("_tracer", "name", "_cat", "_stall", "_args", "_ann",
                 "_t0", "seconds")

    def __init__(self, tracer, name, cat, stall, args):
        self._tracer = tracer
        self.name = name
        self._cat = cat
        self._stall = stall
        self._args = args
        self.seconds = 0.0

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self._args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.seconds = t1 - self._t0
        if self._stall is not None:
            self._stall.add(self.name, self.seconds)
        if self._tracer.enabled:
            self._tracer._emit("X", self.name, self._cat,
                               self._tracer._us(self._t0),
                               dur=self.seconds * 1e6, args=self._args)
        return False


class NullTracer:
    """Tracer that keeps no events: spans still annotate the profiler's
    trace and credit a stall clock.  ``enabled`` is the one flag consumers
    branch on (e.g. the engine only attaches a stall report when it is
    True)."""

    enabled = False
    __slots__ = ()

    def span(self, name, cat="", *, stall=None, **args):
        return _Span(self, name, cat, stall, args)

    def complete(self, name, cat="", duration_s=0.0, **args):
        pass

    def instant(self, name, cat="", **args):
        pass

    def events(self):
        return []

    @property
    def dropped(self):
        return 0


NULL_TRACER = NullTracer()


class Tracer:
    """In-memory span recorder (Chrome ``trace_event`` shaped dicts).

    ``max_events`` bounds memory on graph-sized runs: past the cap new
    events are counted in ``dropped`` instead of stored (the stall report
    and metrics registry keep their own accumulators, so attribution
    survives a capped trace).
    """

    enabled = True

    def __init__(self, max_events: int = 500_000):
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._dropped = 0
        self._max_events = max_events
        self._pid = os.getpid()
        self._named_tids: set[int] = set()
        self._t0 = time.perf_counter()

    # -- clock -----------------------------------------------------------
    def _us(self, t: float) -> float:
        """``perf_counter`` reading -> microseconds since the epoch."""
        return (t - self._t0) * 1e6

    # -- recording -------------------------------------------------------
    def _emit(self, ph, name, cat, ts, *, dur=None, args=None):
        tid = threading.get_ident()
        with self._lock:
            if tid not in self._named_tids:
                self._named_tids.add(tid)
                self._events.append({
                    "ph": "M", "name": "thread_name", "pid": self._pid,
                    "tid": tid,
                    "args": {"name": threading.current_thread().name}})
            if len(self._events) >= self._max_events:
                self._dropped += 1
                return
            ev = {"ph": ph, "name": name, "cat": cat or "repro",
                  "pid": self._pid, "tid": tid, "ts": ts}
            if dur is not None:
                ev["dur"] = max(dur, 0.0)
            if ph == "i":
                ev["s"] = "t"
            if args:
                ev["args"] = args
            self._events.append(ev)

    def span(self, name: str, cat: str = "", *, stall=None, **args):
        """Context manager timing one region (see the module docstring);
        ``stall`` is the ``StallClock`` its seconds are credited to."""
        return _Span(self, name, cat, stall, args)

    def complete(self, name: str, cat: str = "", duration_s: float = 0.0,
                 **args):
        """Record a span of ``duration_s`` seconds that ends *now*, for an
        event measured elsewhere (it reaches no profiler trace).  The
        start is clamped to the tracer's epoch so a duration measured
        before the tracer existed still yields a valid (ts >= 0) event."""
        now = self._us(time.perf_counter())
        self._emit("X", name, cat, max(now - duration_s * 1e6, 0.0),
                   dur=duration_s * 1e6, args=args)

    def instant(self, name: str, cat: str = "", **args):
        self._emit("i", name, cat, self._us(time.perf_counter()), args=args)

    # -- export ----------------------------------------------------------
    def events(self) -> list[dict]:
        """Snapshot of recorded events (copy — safe to serialize while
        other threads keep tracing)."""
        with self._lock:
            return list(self._events)

    @property
    def dropped(self) -> int:
        return self._dropped


# ---------------------------------------------------------------------------
# process-global active tracer
# ---------------------------------------------------------------------------

_ACTIVE: list = [NULL_TRACER]


def get_tracer():
    """The innermost tracer activated via ``use_tracer`` (NULL_TRACER when
    none is active).  Worker threads see the same tracer as the thread
    that activated it."""
    return _ACTIVE[-1]


@contextlib.contextmanager
def use_tracer(tracer):
    """Make ``tracer`` the process-global active tracer for the block.
    ``None`` is accepted and treated as NULL_TRACER (so callers can pass
    an optional through unconditionally)."""
    _ACTIVE.append(NULL_TRACER if tracer is None else tracer)
    try:
        yield _ACTIVE[-1]
    finally:
        _ACTIVE.pop()
