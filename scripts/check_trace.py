"""Assert a Chrome trace_event export covers the serving pipeline.

CI's observability leg runs the serving benchmark with ``--trace`` and then
runs this over the exported JSON: the trace must load, contain complete
("ph": "X") events, and cover every required stage of the pipeline —
ingest, at least one core-repair phase (region / candidates / descend /
fallback), and query flushes with their device wait (``flush.wait``) —
plus the retrain stages when the run included retraining. A refactor that
silently drops a span (or renames one without updating its consumers)
fails here instead of producing quietly-empty traces.

Usage::

    python scripts/check_trace.py results/serve_trace.json
    python scripts/check_trace.py results/serve_trace.json --expect-retrain
    python scripts/check_trace.py results/serve_trace.json --expect-recovery
    python scripts/check_trace.py results/serve_trace.json --expect-topk
"""
from __future__ import annotations

import argparse
import json
import sys

REQUIRED = [
    "serve.ingest",
    "serve.flush",
    "flush.wait",
    "graph.add_edges",
]
# block repair always runs the region phase; which later phase fires
# (candidates/descend vs fallback) depends on region size, so any one of
# them satisfies the repair requirement
REPAIR_ANY = ["repair.candidates", "repair.descend", "repair.fallback"]
RETRAIN_REQUIRED = [
    "retrain.plan",
    "retrain.train",
    "retrain.align",
    "retrain.propagate",
    "retrain.swap",
]
RECOVERY_REQUIRED = [
    "recovery.wal_append",
    "recovery.snapshot",
    "recovery.restore",
    "recovery.replay",
]
# the query-engine leg (--topk benchmark runs): the retrieval span and the
# scoring kernel's dispatch-to-readback inside it
TOPK_REQUIRED = ["serve.topk", "topk.wait"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", help="Chrome trace_event JSON to check")
    ap.add_argument("--expect-retrain", action="store_true",
                    help="also require the retrain stage spans")
    ap.add_argument("--expect-recovery", action="store_true",
                    help="also require the WAL/snapshot/restore/replay "
                         "recovery spans")
    ap.add_argument("--expect-topk", action="store_true",
                    help="also require the serve.topk retrieval span and "
                         "its topk.wait kernel dispatch")
    args = ap.parse_args(argv)

    with open(args.trace) as f:
        doc = json.load(f)
    events = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    names = {e["name"] for e in events}
    print(f"[check-trace] {args.trace}: {len(events)} complete events, "
          f"{len(names)} span kinds: {', '.join(sorted(names))}")

    missing = [n for n in REQUIRED if n not in names]
    if "repair.region" not in names:
        missing.append("repair.region")
    if not any(n in names for n in REPAIR_ANY):
        missing.append(" | ".join(REPAIR_ANY))
    if args.expect_retrain:
        missing += [n for n in RETRAIN_REQUIRED if n not in names]
    if args.expect_recovery:
        missing += [n for n in RECOVERY_REQUIRED if n not in names]
    if args.expect_topk:
        missing += [n for n in TOPK_REQUIRED if n not in names]
    if missing:
        print(f"[check-trace] FAIL: missing spans: {missing}")
        return 1

    bad = [e for e in events
           if "ts" not in e or "dur" not in e or e["dur"] < 0]
    if bad:
        print(f"[check-trace] FAIL: {len(bad)} events without valid ts/dur")
        return 1
    # nesting sanity: at least one repair span strictly inside an ingest span
    ingests = [e for e in events if e["name"] == "serve.ingest"]
    repairs = [e for e in events if e["name"].startswith("repair.")]
    nested = any(
        i["ts"] <= r["ts"] and r["ts"] + r["dur"] <= i["ts"] + i["dur"]
        for r in repairs for i in ingests
    )
    if repairs and not nested:
        print("[check-trace] FAIL: no repair span nests inside an ingest "
              "span — the span hierarchy is broken")
        return 1
    print("[check-trace] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
