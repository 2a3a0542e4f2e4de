"""Roofline share of the 2PS-L two-candidate scoring body, %.

The least time the chip needs for the body's edges (Algorithm 2's bytes
per edge over the peak memory bandwidth, ``bench.work``; edges placed by
pre-partitioning count only the test that skips them, at the share the
reference found) divided by the device time of the body's whole
executions in the trace.  One execution covers one chunk of the stream.
Nothing when the slices hold none.
"""
from bench import work

MODULE = "jit__score_chunk"


def read(ctx):
    runs, seconds = ctx.body(MODULE)
    scored = ctx.facts.get("scored_share")
    if not runs or seconds <= 0 or scored is None:
        return None
    least = work.score_chunk_bytes(runs * ctx.traffic["chunk"], scored) / \
        ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
