"""Roofline share of the clustering scan body, %.

The least time the chip needs for the body's edges (Algorithm 1's bytes
per edge over the peak memory bandwidth, ``bench.work``) divided by the
device time of the body's whole executions in the trace.  One execution
covers one chunk of the stream.  Nothing when the slices hold none.
"""
from bench import work

MODULE = "jit__cluster_chunk_step"


def read(ctx):
    runs, seconds = ctx.body(MODULE)
    if not runs or seconds <= 0:
        return None
    least = work.cluster_step_bytes(runs * ctx.traffic["chunk"]) / \
        ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
