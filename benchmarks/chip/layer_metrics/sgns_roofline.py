"""Share of their roofline that the SGNS loss kernels reach, forward and
backward together.

Each launch of ``kernels/sgns.py``'s Pallas kernels shows in the device
trace as an ``XLA Ops`` event of the training step, a ``tpu_custom_call``
named ``sgns_loss_fwd_pallas`` or ``sgns_loss_bwd_pallas``, whose first
three operands are the centre rows (B, D), the context rows (B, D) and the
negatives (B, K*D) (then, backward, the loss cotangent (B, 1)); the forward
writes the (B, 1) loss, the backward gradients of the first three. Per
launch, from those shapes and their layouts:

* operations: forward ``2*B*D*(K+1)`` (K+1 dot products); backward
  ``B*D*(5*K+4)`` (the K+1 dot products again, the centre gradient's
  multiply-adds, the context and negative gradients);
* bytes: one pass over every operand and output that lies in HBM. The
  compiler may place a kernel's operands or outputs in the core's own
  memory (VMEM, memory space 1: ``S(1)`` in the layout); those cross no
  HBM in the kernel and are not counted. All in HBM, at B = 4096, D = 128,
  K = 5 in float32, that is ~14.7 MB forward and ~29.4 MB backward.

Least time is the larger of operations at the bf16 peak and HBM bytes at
the HBM peak. The element sums run on the vector unit, below the bf16
matrix peak, so the operations bound is a lower bound. Share = least time
summed over launches over their device time.
"""
import re

from benchmarks.chip.trace_reduce import op_base

KERNELS = ("sgns_loss_fwd_pallas", "sgns_loss_bwd_pallas")
_TENSOR = re.compile(r"\b(f32|bf16)\[(\d+),(\d+)\](\{[^}]*\})?")
_BYTES = {"f32": 4, "bf16": 2}


def flops_of(fwd: bool, B: int, D: int, K: int) -> int:
    return 2 * B * D * (K + 1) if fwd else B * D * (5 * K + 4)


def _tensors(text: str):
    """(element bytes, rows, cols, in VMEM) of each 2-D tensor in text."""
    return [(_BYTES[t], int(r), int(c), "S(1)" in (lay or ""))
            for t, r, c, lay in _TENSOR.findall(text)]


def _operands(tail: str) -> str:
    """The operand list that opens ``tail``, up to its closing parenthesis;
    the layout constraints when the list names operands without shapes."""
    depth = 1
    for i, ch in enumerate(tail):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            break
    ops, rest = tail[:i], tail[i:]
    if _TENSOR.search(ops):
        return ops
    return rest.partition("operand_layout_constraints=")[2]


def hbm_bytes(text: str) -> int:
    """Bytes of the launch's outputs and operands that lie in HBM."""
    head, _, tail = text.partition("custom-call(")
    return sum(e * r * c for e, r, c, vmem in
               _tensors(head) + _tensors(_operands(tail)) if not vmem)


def launches(summary):
    """(fwd, B, D, K, HBM bytes, seconds) of every SGNS kernel launch in
    the window."""
    out = []
    for _key, op in summary.ops:
        name = op_base(op.name)
        if name not in KERNELS or "tpu_custom_call" not in op.name:
            continue
        args = _tensors(_operands(op.name.partition("custom-call(")[2]))
        if len(args) < 3:
            continue
        (_, B, D, _), (_, _, KD, _) = args[0], args[2]
        out.append((name == KERNELS[0], B, D, KD // D, hbm_bytes(op.name),
                    op.dur))
    return out


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    runs = launches(ctx.trace)
    seconds = sum(r[-1] for r in runs)
    if not runs or seconds <= 0:
        return None
    least = sum(max(flops_of(f, B, D, K) / ctx.peaks["bf16_flops_per_s"],
                    nbytes / ctx.peaks["hbm_bytes_per_s"])
                for f, B, D, K, nbytes, _ in runs)
    return 100.0 * least / seconds
