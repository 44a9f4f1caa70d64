"""Milliseconds of ``repair.descend`` per block applied in the window,
outside re-peels.

The program records one ``repair.descend`` span per occurrence of the phase
(``serve/kcore_inc.py`` ``IncrementalCore._tick``). On the TPU a re-peel
is a fused descent over every node, and its own ``repair.candidates`` and
``repair.descend`` spans lie inside its ``repair.fallback`` span; those
are counted by ``repair.fallback_ms_per_block`` and left out here, as the
program's own ``phase_report()`` leaves them out. The total is divided by
the blocks the window applied.
"""
from benchmarks.chip.layer import span_seconds


def read(ctx):
    blocks = ctx.counters.get("blocks", 0)
    if ctx.kind != "ingest" or not blocks:
        return None
    return span_seconds(ctx, "repair.descend", outside="repair.fallback") \
        / blocks * 1e3
