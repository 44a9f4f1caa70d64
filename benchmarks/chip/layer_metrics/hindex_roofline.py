"""Share of its roofline that the fused descent's h-index kernel reaches.

Each launch of ``kernels/hindex.py``'s Pallas kernel shows in the device
trace as an ``XLA Ops`` event ``%h_index_pallas.<i> = s32[1,R] custom-call(
s32[W,R] ..., s32[1,R] ...)``. Its least time is bound by bytes: one pass over
its operands, ``vals_t`` (W, R) int32 and ``est`` (1, R), plus its output
(1, R), is ``bytes_of(W, R) = 4 * (W * R + 2 * R)`` at the HBM peak. The
kernel's repeated passes over the block in VMEM are not counted, so the
share cannot pass 100%. It does no floating-point work.

Share = sum over launches of least time / sum of their device time.
"""
import re

from benchmarks.chip.trace_reduce import op_base

_SHAPES = re.compile(r"custom-call\(s32\[(\d+),(\d+)\]")


def bytes_of(W: int, R: int) -> int:
    return 4 * (W * R + 2 * R)


def launches(summary):
    """(W, R, seconds) of every h-index kernel launch in the window."""
    out = []
    for _key, op in summary.ops:
        if op_base(op.name) != "h_index_pallas" \
                or "tpu_custom_call" not in op.name:
            continue
        m = _SHAPES.search(op.name)
        if m:
            out.append((int(m.group(1)), int(m.group(2)), op.dur))
    return out


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    runs = launches(ctx.trace)
    seconds = sum(s for _, _, s in runs)
    if not runs or seconds <= 0:
        return None
    least = sum(bytes_of(W, R) for W, R, _ in runs) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
