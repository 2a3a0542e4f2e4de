"""Engine stage ``device_wait``: seconds per job, mean over the window's jobs.

Read from the partition CLI's ``stages_s``, summed over the job's
passes: the main thread in ``block_until_ready`` on each chunk's
assignment, and on the pass's state at its end. Each second comes from
an ``engine.device_wait`` span credited to its pass's stall clock, on
every job, tracing off.
"""
from bench import stages


def read(ctx):
    return stages.mean_stage(ctx, "device_wait")
