"""Median milliseconds of one ``link_scores`` call in the window.

The program records one ``serve.link`` span per call (``serve/service.py``):
the flush of the pairs' distinct endpoints (``serve.flush``) and the cosine
scoring of the pairs (``link.score``), ending in host arrays.
"""
from benchmarks.chip.layer import percentile, span_durations


def read(ctx):
    p = percentile(span_durations(ctx, "serve.link"), 50)
    return None if ctx.kind != "query" or p is None else p * 1e3
