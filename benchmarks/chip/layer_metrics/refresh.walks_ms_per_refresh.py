"""Milliseconds per refresh of CoreWalk budgets, the k0-core's ELL table
and the walk corpus (``retrain.walks`` spans)."""
from benchmarks.chip.refresh_spans import ms_per_refresh


def read(ctx):
    return ms_per_refresh(ctx, "retrain.walks")
