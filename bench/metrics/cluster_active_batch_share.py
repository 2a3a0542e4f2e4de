"""Clustering micro-batches that moved a vertex, %, mean over the jobs.

100 * ``cluster_active_batches`` / ``cluster_batches`` from the partition
CLI's report: of the 128-edge micro-batches the clustering scan ran
(padding included), those in which at least one vertex moved.  Every
micro-batch pays the |V|-wide table updates; the rest pay them for
nothing.
"""
from bench import stages


def read(ctx):
    return stages.mean_share(ctx, "cluster_active_batches",
                             lambda j: j["cluster_batches"])
