"""Device trace of short slices of a job, and its reduction to numbers.

A whole job is far too long to trace (the clustering scan alone records
about a thousand device operations per millisecond), so ``SliceTracer``
opens the profiler for a fraction of a second at set offsets into a job,
from a thread of its own, and keeps each slice as serialized XSpace.

``reduce_xspace`` turns one slice into a ``Slice``:

* the window: from the start of the first device operation to the end of
  the last one;
* busy: the union of the device operations' intervals in the window;
* the executions of each jitted program (XLA module), without the first
  and the last on the device, which the slice's edges may have cut;
* device time per operation, and each idle gap named by the innermost
  event of the host thread that dispatches the programs, at the gap's
  midpoint ("no host event" where that thread was in plain Python).
"""
from __future__ import annotations

import re
import threading
import time
from collections import Counter
from dataclasses import dataclass, field

_MODULE_ID = re.compile(r"\(\d+\)$")
_OP_NAME = re.compile(r"^%?([^ =]+)")
#: gaps shorter than this are scheduling jitter, not waiting on the host
MIN_GAP_NS = 5_000


@dataclass
class Slice:
    device: str = ""
    window_ns: float = 0.0
    busy_ns: float = 0.0
    #: module name -> durations (ns) of its whole executions in the slice
    modules: dict = field(default_factory=dict)
    ops: Counter = field(default_factory=Counter)       # op -> ns
    gaps: Counter = field(default_factory=Counter)      # host activity -> ns


def module_name(event_name: str) -> str:
    """``jit__score_chunk(1234)`` -> ``jit__score_chunk``."""
    return _MODULE_ID.sub("", event_name)


def _union(intervals):
    """(total covered length, merged intervals) of (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def _dispatch_thread(planes) -> list:
    """Events of the host thread that dispatches the jitted programs (the
    one with the most ``PjitFunction`` events)."""
    threads = [_events(line) for plane in planes
               if plane.name.startswith("/host") for line in plane.lines]
    return max(threads, default=[], key=lambda evs: sum(
        e[0].startswith("PjitFunction(") for e in evs))


def _activity(events, start, end) -> str:
    """The innermost host event that spans the gap's midpoint."""
    mid = (start + end) / 2
    best = None
    for name, s, e in events:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2].lstrip("$") if best else "no host event"


def reduce_planes(planes) -> list:
    """One ``Slice`` per device plane of the trace."""
    host = _dispatch_thread(planes)
    out = []
    for plane in planes:
        if not plane.name.startswith("/device:") or \
                plane.name.startswith("/device:CUSTOM"):
            continue
        lines = {line.name: _events(line) for line in plane.lines}
        mods = sorted(lines.get("XLA Modules", []), key=lambda e: e[1])
        ops = lines.get("XLA Ops") or mods
        if not mods:
            continue
        lo = min(e[1] for e in mods + ops)
        hi = max(e[2] for e in mods + ops)
        sl = Slice(device=plane.name, window_ns=hi - lo)
        sl.busy_ns, merged = _union((s, e) for _, s, e in ops)
        for name, s, e in mods[1:-1]:
            sl.modules.setdefault(module_name(name), []).append(e - s)
        for name, s, e in ops:
            m = _OP_NAME.match(name)
            sl.ops[m.group(1) if m else name] += e - s
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            if s1 - e0 >= MIN_GAP_NS:
                sl.gaps[_activity(host, e0, s1)] += s1 - e0
        out.append(sl)
    return out


def reduce_xspace(xspace: bytes) -> list:
    import jax
    data = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    return reduce_planes(list(data.planes))



class SliceTracer:
    """Opens the profiler at ``(offset_s, length_s)`` marks, counted from
    ``start()``, on a thread of its own; ``join()`` returns the slices as
    serialized XSpace."""

    def __init__(self, marks):
        self.marks = sorted(marks)
        self.xspaces: list[bytes] = []
        self.errors: list[BaseException] = []
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-slice-tracer")

    def start(self):
        self._t0 = time.perf_counter()
        self._thread.start()

    def _run(self):
        import jax
        from jax._src import xla_bridge
        from jax._src.lib import _profiler
        xla_bridge.get_backend()
        options = jax.profiler.ProfileOptions()
        # the Python tracer holds the interpreter for a tenth of a second
        # when it starts, long enough to starve the device in the slice
        options.python_tracer_level = 0
        try:
            for offset, length in self.marks:
                time.sleep(max(0.0, self._t0 + offset - time.perf_counter()))
                session = _profiler.ProfilerSession(options)
                time.sleep(length)
                self.xspaces.append(session.stop())
        except Exception as e:  # noqa: BLE001 — re-raised by join()
            self.errors.append(e)

    def join(self, timeout: float = 300.0) -> list:
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("profiler slices did not finish")
        if self.errors:
            raise self.errors[0]
        return self.xspaces
