"""95th percentile of a request's queueing time, in milliseconds: from its
scheduled arrival to the start of the call that serves it, on the host clock
of the benchmark's own loop. It shows how late the open loop dispatched,
so that a starved loop is not read as a fast service.
"""
from benchmarks.chip.layer import percentile


def read(ctx):
    q = ctx.samples.get("queue_ms")
    return None if ctx.kind != "query" or q is None else percentile(q, 95)
