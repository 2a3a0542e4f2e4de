"""Stall attribution for the engine's three-stage pipeline.

The engine overlaps read (prefetch thread), device dispatch, and
writeback; ROADMAP decisions like "grow ``pipeline_depth`` until
writeback stops stalling" need to know which stage the stream actually
waits on.  A ``StallClock`` accumulates, per pass over the edge stream,
the seconds of each chunk stage (``CHUNK_STAGES``), credited by the
engine's ``engine.<stage>`` spans on whatever thread runs them:

* ``read`` — producer-side chunk read / decode (the prefetch thread at
  depth >= 2);
* ``queue_wait`` — the consumer blocked on the prefetch queue;
* ``dispatch`` — pad + ``chunk_fn`` host time;
* ``device_wait`` — ``block_until_ready`` on a chunk's assignment, and
  the end-of-pass drain;
* ``fetch`` — the device-to-host copy of a chunk's assignment;
* ``write`` — the assignment rows written to their sink;
* ``fold`` — the pass's host-side replica fold (``StreamPass.host_fold``).

These are the run's stage seconds (``PartitionRunResult.stages``, the CLI
report's ``stages_s``) and each pass's ``attribution``.  They roll up into
the pipeline's three ``STAGES``: ``prefetch`` is busy reading,
``dispatch`` dispatching, ``writeback`` waiting on the device, fetching,
writing and folding.

``StallClock.report`` rolls one pass into a ``PassStall``; the run-level
``PipelineStallReport`` aggregates passes and renders the verdict.  For
every stage ``busy_frac + idle_frac == 1.0`` exactly (fractions are of
the pass wall time, busy clamped to wall), and the **critical stage** is
the stage with the largest aggregate busy time — the one a deeper
pipeline cannot hide.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

__all__ = ["StallClock", "PassStall", "PipelineStallReport", "STAGES",
           "CHUNK_STAGES"]

#: The engine's pipeline stages, in stream order.
STAGES = ("prefetch", "dispatch", "writeback")

#: The per-chunk stages a pass's spans time, in stream order.
CHUNK_STAGES = ("read", "queue_wait", "dispatch", "device_wait", "fetch",
                "write", "fold")

#: pipeline stage -> the chunk stages it is busy in (its chunk count is
#: the first one's)
_BUSY = {"prefetch": ("read",), "dispatch": ("dispatch",),
         "writeback": ("fetch", "device_wait", "write", "fold")}


class StallClock:
    """Thread-safe per-pass accumulator (one instance per StreamPass)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds: dict[str, float] = {}
        self.chunks: dict[str, int] = {}
        self._t0 = time.perf_counter()

    def add(self, span: str, seconds: float):
        """Credit ``seconds`` of one span to its stage: the part of the
        span's name after the last dot (``engine.fold`` -> ``fold``)."""
        stage = span.rpartition(".")[2]
        with self._lock:
            self.seconds[stage] = self.seconds.get(stage, 0.0) + seconds
            self.chunks[stage] = self.chunks.get(stage, 0) + 1

    def report(self, phase: str) -> "PassStall":
        """Close the pass: wall time is now - construction time."""
        wall = time.perf_counter() - self._t0
        with self._lock:
            return PassStall(phase=phase, wall_seconds=wall,
                             attribution=dict(self.seconds),
                             chunks=dict(self.chunks))


def _busy(attribution: dict, chunks: dict) -> tuple[dict, dict]:
    """Chunk-stage seconds and counts -> pipeline-stage busy seconds and
    chunk counts."""
    busy = {s: sum(attribution.get(c, 0.0) for c in cs)
            for s, cs in _BUSY.items()}
    count = {s: chunks.get(cs[0], 0) for s, cs in _BUSY.items()}
    return busy, count


def _stage_fractions(busy: dict, chunks: dict, wall: float) -> dict:
    stages = {}
    for s in STAGES:
        b = min(busy.get(s, 0.0), wall) if wall > 0 else 0.0
        frac = (b / wall) if wall > 0 else 0.0
        stages[s] = {"busy_s": busy.get(s, 0.0),
                     "idle_s": max(wall - b, 0.0),
                     "busy_frac": frac, "idle_frac": 1.0 - frac,
                     "chunks": chunks.get(s, 0)}
    return stages


def _critical(stages: dict) -> str:
    return max(stages, key=lambda s: stages[s]["busy_s"])


@dataclass
class PassStall:
    """Stall accounting for one sweep over the edge stream."""

    phase: str
    wall_seconds: float
    attribution: dict = field(default_factory=dict)  # chunk stage -> s
    chunks: dict = field(default_factory=dict)  # chunk stage -> spans

    def stages(self) -> dict:
        return _stage_fractions(*_busy(self.attribution, self.chunks),
                                self.wall_seconds)

    def to_dict(self) -> dict:
        stages = self.stages()
        return {"phase": self.phase,
                "wall_s": self.wall_seconds,
                "stages": stages,
                "attribution": dict(self.attribution),
                "chunks": dict(self.chunks),
                "critical_stage": _critical(stages)}


@dataclass
class PipelineStallReport:
    """All passes of one run, plus the aggregate verdict.

    ``to_dict()`` is the JSON-safe shape attached to
    ``PartitionRunResult.extras["stall_report"]`` and artifact manifests
    (see docs/observability.md for the field table); ``from_dict``
    round-trips it.  Per stage, ``busy_frac + idle_frac == 1.0``.
    """

    passes: list = field(default_factory=list)    # [PassStall]

    @property
    def wall_seconds(self) -> float:
        return sum(p.wall_seconds for p in self.passes)

    def stages(self) -> dict:
        busy: dict[str, float] = {}
        chunks: dict[str, int] = {}
        for p in self.passes:
            for s, v in p.attribution.items():
                busy[s] = busy.get(s, 0.0) + v
            for s, n in p.chunks.items():
                chunks[s] = chunks.get(s, 0) + n
        return _stage_fractions(*_busy(busy, chunks), self.wall_seconds)

    def stage_seconds(self) -> dict:
        """``{phase: {chunk stage: seconds}}``, passes of one phase
        summed."""
        out: dict[str, dict] = {}
        for p in self.passes:
            acc = out.setdefault(p.phase, {})
            for s, v in p.attribution.items():
                acc[s] = acc.get(s, 0.0) + v
        return out

    @property
    def critical_stage(self) -> str:
        return _critical(self.stages())

    @property
    def verdict(self) -> str:
        """Human verdict: which stage bounds the pipeline, and how hard
        (e.g. ``'dispatch-bound (78% busy)'``)."""
        stages = self.stages()
        crit = _critical(stages)
        return f"{crit}-bound ({stages[crit]['busy_frac']:.0%} busy)"

    def to_dict(self) -> dict:
        return {"wall_s": self.wall_seconds,
                "stages": self.stages(),
                "critical_stage": self.critical_stage,
                "verdict": self.verdict,
                "passes": [p.to_dict() for p in self.passes]}

    @classmethod
    def from_dict(cls, d: dict) -> "PipelineStallReport":
        passes = [PassStall(phase=p["phase"], wall_seconds=p["wall_s"],
                            attribution=dict(p["attribution"]),
                            chunks=dict(p["chunks"]))
                  for p in d.get("passes", [])]
        return cls(passes=passes)
