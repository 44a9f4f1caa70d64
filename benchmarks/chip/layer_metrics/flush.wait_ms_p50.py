"""Median milliseconds a query flush batch waits on the device.

The program records one ``flush.wait`` span inside each ``serve.flush``
(``serve/service.py`` ``EmbeddingService._flush_batch``): from the call of
the fused gather/cold-start/write-back program to the end of the readback
of its answers, i.e. dispatch, blocking sync and copy to the host. The rest
of the flush is host work (``flush.stage``, ``flush.commit``).
"""
from benchmarks.chip.layer import percentile, span_durations


def read(ctx):
    p = percentile(span_durations(ctx, "flush.wait"), 50)
    return None if ctx.kind != "query" or p is None else p * 1e3
