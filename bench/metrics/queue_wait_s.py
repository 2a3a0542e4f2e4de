"""Engine stage ``queue_wait``: seconds per job, mean over the window's jobs.

Read from the partition CLI's ``stages_s``, summed over the job's
passes: the main thread blocked on the prefetch queue, waiting for the
reader thread's next chunk. Each second comes from an
``engine.queue_wait`` span credited to its pass's stall clock, on every
job, tracing off.
"""
from bench import stages


def read(ctx):
    return stages.mean_stage(ctx, "queue_wait")
