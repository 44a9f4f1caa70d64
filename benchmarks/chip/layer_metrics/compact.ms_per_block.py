"""Milliseconds of ELL-mirror compaction per block applied in the window.

The program records one ``graph.compact`` span per compaction
(``serve/stream.py`` ``DynamicGraph.compact``): the host rebuild of the
mirror (``compact.build``) and its device upload and swap into place
(``compact.upload``). The total over the window is divided by the blocks
it applied, inserts and retractions alike.
"""
from benchmarks.chip.layer import span_seconds


def read(ctx):
    blocks = ctx.counters.get("blocks", 0)
    if ctx.kind != "ingest" or not blocks:
        return None
    return span_seconds(ctx, "graph.compact") / blocks * 1e3
