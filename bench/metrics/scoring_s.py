"""Engine stage ``scoring``: seconds per job, mean over the window's jobs.

Read from the partition CLI's ``timings_s``, the engine's host-clock
timer, whose stages are disjoint and each end on the device's results.
"""


def read(ctx):
    return ctx.mean_timing("scoring")
