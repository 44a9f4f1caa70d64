"""Span totals per refresh, for the readers of the refresh cell's metrics.

The program records one ``serve.retrain`` span per refresh
(``EmbeddingService.maybe_retrain``). Its children partition it: the
transactional copy of the store (``retrain.rollback_copy``) and the stages of
``Retrainer.run`` (``retrain.plan``, ``.walks``, ``.train``, ``.align``,
``.propagate``, ``.swap``); ``retrain.train`` holds the SGNS step loop
(``sgns.steps``) and its final readback (``sgns.wait``).
"""
from typing import Optional

from benchmarks.chip.layer import LayerContext, span_seconds

HOST_STAGES = ("retrain.rollback_copy", "retrain.plan", "retrain.align",
               "retrain.propagate", "retrain.swap")


def refreshes(ctx: LayerContext) -> int:
    """Refreshes that began in the window of a refresh cell; 0 elsewhere."""
    if ctx.kind != "refresh":
        return 0
    return sum(1 for s in ctx.spans if s["name"] == "serve.retrain")


def ms_per_refresh(ctx: LayerContext, *names: str) -> Optional[float]:
    """Milliseconds of the spans ``names`` over the window, per refresh;
    None without a refresh or without any such span."""
    n = refreshes(ctx)
    if not n or not any(s["name"] in names for s in ctx.spans):
        return None
    return sum(span_seconds(ctx, name) for name in names) / n * 1e3
