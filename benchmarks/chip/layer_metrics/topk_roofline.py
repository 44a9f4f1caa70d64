"""Share of its roofline that the top-k retrieval kernel reaches.

Each launch of ``kernels/topk.py``'s Pallas kernel runs in its own program
``jit_topk_pallas``; its ``XLA Ops`` event is the ``tpu_custom_call`` whose
operands are the queries (Q, D), the table (N, D) and the validity bias
(1, N), all float32, and whose outputs are the (Q, Kp) scores and indices.
Its least time is the larger of two bounds:

* operations, ``flops_of(Q, N, D) = 2 * Q * N * D`` at the bf16 peak;
* bytes, ``bytes_of(Q, N, D, Kp) = 4 * (N * D + Q * D + N) + 8 * Q * Kp``,
  one pass over the operands and the outputs, at the HBM peak.

At graph500-s14's shapes (Q = 64, N ~ 12.5k rows, D = 128) the bytes
bound holds: ~6.7 MB take ~8 us at 819 GB/s, against ~1 us for the 0.21
GFLOP at 197 TFLOP/s. Shapes are read from the event, padding included, as
the kernel reads them.
"""
import re

_F32 = re.compile(r"f32\[(\d+),(\d+)\]")


def flops_of(Q: int, N: int, D: int) -> int:
    return 2 * Q * N * D


def bytes_of(Q: int, N: int, D: int, Kp: int) -> int:
    return 4 * (N * D + Q * D + N) + 8 * Q * Kp


def launches(summary):
    """(Q, N, D, Kp, seconds) of every top-k kernel launch in the window."""
    out = []
    for key, op in summary.ops:
        if not key.startswith("jit_topk_pallas/") \
                or "tpu_custom_call" not in op.name:
            continue
        head, _, tail = op.name.partition("custom-call(")
        outs, args = _F32.findall(head), _F32.findall(tail)
        if outs and len(args) >= 2:
            (Q, D), (N, _) = (tuple(map(int, a)) for a in args[:2])
            out.append((Q, N, D, int(outs[0][1]), op.dur))
    return out


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    runs = launches(ctx.trace)
    seconds = sum(r[-1] for r in runs)
    if not runs or seconds <= 0:
        return None
    least = sum(max(flops_of(Q, N, D) / ctx.peaks["bf16_flops_per_s"],
                    bytes_of(Q, N, D, Kp) / ctx.peaks["hbm_bytes_per_s"])
                for Q, N, D, Kp, _ in runs)
    return 100.0 * least / seconds
