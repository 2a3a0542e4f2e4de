"""Writeback stage ``fold``: seconds per job, mean over the window's jobs.

Read from the partition CLI's ``stages_s``, summed over the job's
passes: folding each chunk's replicas into the host's bit matrix
(``StreamPass.host_fold``). Each second comes from an ``engine.fold``
span credited to its pass's stall clock. ``fetch`` + ``write`` +
``fold`` is the host's writeback time that ``writeback_s`` reads.
"""
from bench import stages


def read(ctx):
    return stages.mean_stage(ctx, "fold")
