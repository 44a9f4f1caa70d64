"""Milliseconds per refresh of its host stages: the rollback copy of the
store, the plan (snapshot and subcore), the Procrustes alignment, the §2.2
propagation and the chunked swap (``HOST_STAGES`` spans)."""
from benchmarks.chip.refresh_spans import HOST_STAGES, ms_per_refresh


def read(ctx):
    return ms_per_refresh(ctx, *HOST_STAGES)
