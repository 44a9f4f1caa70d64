"""What a per-layer metric reader is given, and how readers are found.

A per-layer metric ``<name>`` is read by ``layer_metrics/<name>.py``, which
defines ``read(ctx: LayerContext) -> float | None``. A reader returns None
when the run gave it nothing to read; the harness then leaves the metric out
of the result line. A share of a roofline is never 0 for want of data.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["LayerContext", "load_reader", "span_seconds", "span_durations",
           "percentile"]


@dataclass
class LayerContext:
    kind: str  # the traffic kind: "ingest" or "query"
    window: Tuple[float, float]  # perf_counter seconds
    spans: List[Dict[str, Any]]  # the program's spans that began in the window
    counters: Dict[str, float] = field(default_factory=dict)
    samples: Dict[str, np.ndarray] = field(default_factory=dict)
    trace: Optional[Any] = None  # trace_reduce.Summary of the window
    peaks: Optional[Dict[str, float]] = None  # peaks.json entry of the device


def span_durations(ctx: LayerContext, name: str) -> np.ndarray:
    """Seconds of each program span named ``name`` in the window."""
    return np.asarray([s["dur"] for s in ctx.spans if s["name"] == name],
                      np.float64)


def span_seconds(ctx: LayerContext, name: str, outside: str = "") -> float:
    """Total seconds of the spans named ``name``, less those that lie inside
    a span named ``outside``."""
    within = [(s["ts"], s["ts"] + s["dur"]) for s in ctx.spans
              if s["name"] == outside]
    return float(sum(
        s["dur"] for s in ctx.spans
        if s["name"] == name and not any(
            a <= s["ts"] and s["ts"] + s["dur"] <= b for a, b in within)))


def percentile(x: np.ndarray, q: float) -> Optional[float]:
    x = np.asarray(x, np.float64)
    return float(np.percentile(x, q)) if x.size else None


def load_reader(chip_dir: Path, name: str):
    path = Path(chip_dir) / "layer_metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} "
                                f"at {path}")
    spec = importlib.util.spec_from_file_location(
        f"layer_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
