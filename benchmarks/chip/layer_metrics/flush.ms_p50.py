"""Median milliseconds of one query flush batch in the window.

The program records one ``serve.flush`` span per static batch
(``serve/service.py`` ``EmbeddingService._flush_batch``); the span ends after
the batch's answers are read back to the host.
"""
from benchmarks.chip.layer import percentile, span_durations


def read(ctx):
    p = percentile(span_durations(ctx, "serve.flush"), 50)
    return None if ctx.kind != "query" or p is None else p * 1e3
