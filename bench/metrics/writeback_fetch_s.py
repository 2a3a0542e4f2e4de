"""Writeback stage ``fetch``: seconds per job, mean over the window's jobs.

Read from the partition CLI's ``stages_s``, summed over the job's
passes: copying each chunk's assignment off the device (``np.asarray``).
Each second comes from an ``engine.fetch`` span credited to its pass's
stall clock. ``fetch`` + ``write`` + ``fold`` is the host's writeback
time that ``writeback_s`` reads.
"""
from bench import stages


def read(ctx):
    return stages.mean_stage(ctx, "fetch")
