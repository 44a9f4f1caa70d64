"""Share of the window in which no operation ran on the device, in percent,
over the refresh cells.

From the profiler trace: one minus the union of the ``XLA Ops`` intervals
of the window over its length (``trace_reduce.summarize``).
"""


def read(ctx):
    if ctx.trace is None or ctx.kind != "refresh":
        return None
    return 100.0 * ctx.trace.idle_share
