"""Engine stage ``writeback``: seconds per job, mean over the window's jobs.

Read from the partition CLI's ``timings_s``: the host's share of the
pipeline's writeback stage (copying each chunk's assignment off the
device, writing it to the assignment file, folding replicas on the host),
as the engine's host-clock timer counts it apart from the other stages.
"""


def read(ctx):
    return ctx.mean_timing("writeback")
