"""Share of the traced slices in which no operation ran on the device, %.

1 - (union of the device operations' intervals) / (slice window), over
all slices of the traced job (``bench.devtrace``).  Nothing when no slice
recorded a device operation.
"""


def read(ctx):
    window = sum(s.window_ns for s in ctx.slices)
    if not window:
        return None
    busy = sum(s.busy_ns for s in ctx.slices)
    return 100.0 * (1.0 - busy / window)
