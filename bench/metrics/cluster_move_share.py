"""Clustering moves per edge streamed, %, mean over the window's jobs.

100 * ``cluster_moves`` / (``edges`` * ``cluster_passes``) from the
partition CLI's report: the vertices the clustering scan moved to another
cluster, counted on the device and read once at the end of the scan, over
the edges it streamed.  Useful work over work attempted: a change to the
scan's speed leaves it where it is.
"""
from bench import stages


def read(ctx):
    return stages.mean_share(ctx, "cluster_moves",
                             lambda j: j["edges"] * j["cluster_passes"])
