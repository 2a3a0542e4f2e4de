"""Writeback stage ``write``: seconds per job, mean over the window's jobs.

Read from the partition CLI's ``stages_s``, summed over the job's
passes: writing each chunk's assignment rows to the assignment file.
Each second comes from an ``engine.write`` span credited to its pass's
stall clock. ``fetch`` + ``write`` + ``fold`` is the host's writeback
time that ``writeback_s`` reads.
"""
from bench import stages


def read(ctx):
    return stages.mean_stage(ctx, "write")
