"""Milliseconds of ``repair.fallback`` per block applied in the window.

The program records one ``repair.fallback`` span per occurrence of the phase
(``serve/kcore_inc.py`` ``IncrementalCore._tick``); their total over
the window is divided by the blocks the window applied.
"""
from benchmarks.chip.layer import span_seconds


def read(ctx):
    blocks = ctx.counters.get("blocks", 0)
    if ctx.kind != "ingest" or not blocks:
        return None
    return span_seconds(ctx, "repair.fallback") / blocks * 1e3
