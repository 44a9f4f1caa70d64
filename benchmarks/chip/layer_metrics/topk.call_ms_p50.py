"""Median milliseconds of one ``top_k_neighbors`` call in the window.

The program records one ``serve.topk`` span per call (``serve/service.py``);
it covers the call's own flush, the scoring kernel and the host merge, and
ends in host arrays.
"""
from benchmarks.chip.layer import percentile, span_durations


def read(ctx):
    p = percentile(span_durations(ctx, "serve.topk"), 50)
    return None if ctx.kind != "query" or p is None else p * 1e3
