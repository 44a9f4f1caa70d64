"""Share of the window, in percent, spent inside refreshes
(``serve.retrain`` spans over the window's length)."""
from benchmarks.chip.layer import span_seconds
from benchmarks.chip.refresh_spans import refreshes


def read(ctx):
    if not refreshes(ctx):
        return None
    lo, hi = ctx.window
    return 100.0 * span_seconds(ctx, "serve.retrain") / (hi - lo)
