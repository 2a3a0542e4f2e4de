"""Artifact save: seconds per job, mean over the window's jobs.

Read from the partition CLI's ``artifact_save_s``: the ``artifact.save``
span around ``PartitionArtifact.save`` (flush the assignment, its sha256,
the manifest), which no engine stage times.
"""
from bench import stages


def read(ctx):
    return stages.mean(j["artifact_save_s"] for j in ctx.jobs
                       if "artifact_save_s" in j)
