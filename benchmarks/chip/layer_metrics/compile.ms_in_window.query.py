"""Milliseconds of JAX traces, lowerings and compiles that began in the
query window (``jit.compile`` spans, merged where they nest). The warm-up
compiles every shape the window serves, so this should read 0.
"""
from benchmarks.chip.compile_spans import compile_ms


def read(ctx):
    return compile_ms(ctx) if ctx.kind == "query" else None
