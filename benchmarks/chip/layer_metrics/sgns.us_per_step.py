"""Microseconds of SGNS training per step: the ``retrain.train`` spans of
the window over the steps they carry (their ``steps`` attribute), so the
warm table and the final readback are shared out over the steps."""
from benchmarks.chip.refresh_spans import refreshes


def read(ctx):
    if not refreshes(ctx):
        return None
    train = [s for s in ctx.spans if s["name"] == "retrain.train"
             and "steps" in s.get("attrs", {})]
    steps = sum(s["attrs"]["steps"] for s in train)
    if not steps:
        return None
    return sum(s["dur"] for s in train) / steps * 1e6
