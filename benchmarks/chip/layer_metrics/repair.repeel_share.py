"""Share of the window's block repairs that the repair policy sent to a
re-peel rather than a fused descent, in percent.

Read from the program's counters (``RepairPolicy.decisions`` of
``serve/kcore_inc.py``) before and after the window. The adaptive policy
chooses from measured phase times, so this share explains much of the spread
of ``ingest_edges_per_s`` between runs.
"""


def read(ctx):
    d = ctx.counters.get("descend_decisions", 0)
    r = ctx.counters.get("repeel_decisions", 0)
    if ctx.kind != "ingest" or not d + r:
        return None
    return 100.0 * r / (d + r)
