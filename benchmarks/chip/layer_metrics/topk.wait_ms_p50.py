"""Median milliseconds a top-k call waits on its scoring kernel.

The program records one ``topk.wait`` span inside each ``serve.topk``
(``serve/service.py`` ``top_k_neighbors``): from the call of the blockwise
top-k kernel (``kernels/topk.py``) to the end of the readback of its scores
and row indices.
"""
from benchmarks.chip.layer import percentile, span_durations


def read(ctx):
    p = percentile(span_durations(ctx, "topk.wait"), 50)
    return None if ctx.kind != "query" or p is None else p * 1e3
