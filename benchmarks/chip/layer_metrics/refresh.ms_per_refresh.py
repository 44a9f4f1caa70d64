"""Milliseconds of one refresh of the k0-core embeddings, on average over
the refreshes of the window (``serve.retrain`` spans: the rollback copy,
walks, SGNS, alignment, propagation and the swap)."""
from benchmarks.chip.refresh_spans import ms_per_refresh


def read(ctx):
    return ms_per_refresh(ctx, "serve.retrain")
