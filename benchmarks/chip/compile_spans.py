"""Milliseconds the program spent in JAX traces, lowerings and compiles.

While tracing is on, the program records each JAX trace, lowering and
backend compile as a ``jit.compile`` span (``repro.obs.trace``: a
``jax.monitoring`` duration listener). A trace of a function nested in
another's trace lies inside the outer one, so the spans are merged into the
length of their union before they are summed.
"""
from benchmarks.chip.layer import LayerContext


def compile_ms(ctx: LayerContext) -> float:
    """Length of the union of the ``jit.compile`` spans that began in the
    window, in milliseconds; 0.0 when none did."""
    spans = sorted((s["ts"], s["ts"] + s["dur"]) for s in ctx.spans
                   if s["name"] == "jit.compile")
    total, end = 0.0, float("-inf")
    for a, b in spans:
        if b > end:
            total += b - max(a, end)
            end = b
    return total * 1e3
