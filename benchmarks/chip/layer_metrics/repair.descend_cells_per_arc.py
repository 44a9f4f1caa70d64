"""Candidate-matrix cells the fused descent sweeps per real arc.

Every ``repair.descend`` span of the fused descent (``serve/kcore_inc.py``
``IncrementalCore._descend_read``) carries ``cells``, the padded cells of
its candidate tiers that each sweep gathers and scans, and ``arcs``, the
sum of its candidates' true degrees. Spans inside a re-peel's
``repair.fallback`` count too: on the TPU a re-peel is the same descent
over every node. The ratio is the sum of ``cells`` over the sum of
``arcs`` in the window; 1 would be a matrix with no padding. A program
whose spans carry no ``arcs`` reads nothing.
"""


def read(ctx):
    if ctx.kind != "ingest":
        return None
    spans = [s["attrs"] for s in ctx.spans if s["name"] == "repair.descend"
             and {"cells", "arcs"} <= s.get("attrs", {}).keys()]
    arcs = sum(a["arcs"] for a in spans)
    if not arcs:
        return None
    return sum(a["cells"] for a in spans) / arcs
