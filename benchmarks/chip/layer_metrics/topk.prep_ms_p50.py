"""Median milliseconds a top-k call spends preparing its scoring inputs.

The program records one ``topk.prep`` span inside each ``serve.topk``
(``serve/service.py`` ``top_k_neighbors``): normalising the queries and the
whole table, and uploading the table's row-validity mask.
"""
from benchmarks.chip.layer import percentile, span_durations


def read(ctx):
    p = percentile(span_durations(ctx, "topk.prep"), 50)
    return None if ctx.kind != "query" or p is None else p * 1e3
