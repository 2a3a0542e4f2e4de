"""Engine stage ``dispatch``: seconds per job, mean over the window's jobs.

Read from the partition CLI's ``stages_s``, summed over the job's
passes: the main thread padding each chunk and dispatching the pass's
jitted chunk program (host time; the device runs asynchronously). Each
second comes from an ``engine.dispatch`` span credited to its pass's
stall clock, on every job, tracing off.
"""
from bench import stages


def read(ctx):
    return stages.mean_stage(ctx, "dispatch")
