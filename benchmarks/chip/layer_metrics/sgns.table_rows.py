"""Rows of the tables the SGNS steps of a refresh train: the mean ``rows``
attribute of the window's ``retrain.train`` spans. The steps train the
corpus's support (the k0-core, padded) where the program sizes the tables
to it, the whole graph where it does not. None where the spans carry no
``rows``."""
from benchmarks.chip.refresh_spans import refreshes


def read(ctx):
    if not refreshes(ctx):
        return None
    rows = [s["attrs"]["rows"] for s in ctx.spans
            if s["name"] == "retrain.train" and "rows" in s.get("attrs", {})]
    if not rows:
        return None
    return sum(rows) / len(rows)
