"""Milliseconds of JAX traces, lowerings and compiles that began in the
ingest window (``jit.compile`` spans, merged where they nest). A padded
descent shape the warm-up did not meet compiles here, on the ingest clock.
"""
from benchmarks.chip.compile_spans import compile_ms


def read(ctx):
    return compile_ms(ctx) if ctx.kind == "ingest" else None
