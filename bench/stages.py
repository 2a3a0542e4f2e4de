"""Per-job numbers of the partition CLI's report, for per-layer readers.

A report carries the engine's stage seconds per pass (``stages_s``:
``{phase: {stage: seconds}}``), the artifact's save time
(``artifact_save_s``) and the 2PS-L clustering counters.  A program that
predates one of them reports nothing, and its reader returns ``None``.
"""
from __future__ import annotations

import numpy as np


def mean(values) -> float | None:
    values = list(values)
    return float(np.mean(values)) if values else None


def mean_stage(ctx, stage: str) -> float | None:
    """Seconds of one chunk stage per job, summed over the job's passes,
    mean over the window's jobs."""
    return mean(sum(sec.get(stage, 0.0) for sec in j["stages_s"].values())
                for j in ctx.jobs if "stages_s" in j)


def mean_share(ctx, key: str, whole) -> float | None:
    """100 * job[key] / whole(job), mean over the window's jobs that
    report ``key``."""
    return mean(100.0 * j[key] / whole(j) for j in ctx.jobs
                if key in j and whole(j))
