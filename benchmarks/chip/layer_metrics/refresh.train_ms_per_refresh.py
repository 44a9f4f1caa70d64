"""Milliseconds per refresh of warm-started SGNS training
(``retrain.train`` spans: the warm table, the step loop, the readback)."""
from benchmarks.chip.refresh_spans import ms_per_refresh


def read(ctx):
    return ms_per_refresh(ctx, "retrain.train")
