"""Device-side observability hooks: profiler traces, dispatch costs, memory.

Three independent hooks. The cost and memory hooks tolerate backends that
report nothing (CPU has no ``memory_stats``); a requested profile does not:

* :func:`device_profile` — context manager around
  ``jax.profiler.start_trace`` / ``stop_trace``, so an ingest sweep or
  query replay can be captured as a full XLA device profile (open the
  resulting directory with TensorBoard or Perfetto). A profile that cannot
  start raises.
* :func:`compiled_cost` — per-dispatch cost of a jitted function on
  concrete arguments via AOT ``lower().compile().cost_analysis()`` (flops
  and bytes accessed, the roofline inputs) plus ``memory_analysis`` byte
  sizes. This is how the Pallas h-index / ellmean dispatches get *measured*
  cost numbers instead of guessed ones.
* :func:`record_memory` — live per-device memory gauges
  (``device_bytes_in_use{device=...}``) from ``Device.memory_stats()``,
  skipping devices that report nothing.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional

import jax

from .metrics import MetricsRegistry, metrics

__all__ = ["device_profile", "compiled_cost", "record_memory"]


@contextlib.contextmanager
def device_profile(logdir: Optional[str]):
    """Capture a ``jax.profiler`` trace of the enclosed block into ``logdir``.

    A ``None``/empty ``logdir`` profiles nothing. A requested trace that
    cannot start or be written raises: a run asked to profile the device
    must not pass without the profile.
    """
    if not logdir:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def compiled_cost(fn, *args, **kwargs) -> Dict[str, Any]:
    """Cost/memory analysis of one jitted dispatch on concrete arguments.

    ``fn`` must be a ``jax.jit``-wrapped callable; ``args``/``kwargs`` are
    example inputs of the shapes the serving path actually dispatches.
    Returns ``{"flops", "bytes_accessed", "argument_bytes", "output_bytes",
    "temp_bytes"}`` with 0.0 where the backend reports nothing, or
    ``{"error": ...}`` when AOT lowering itself is unsupported.
    """
    try:
        compiled = fn.lower(*args, **kwargs).compile()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis() or {}
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception:
        out["flops"] = out["bytes_accessed"] = 0.0
    try:
        ma = compiled.memory_analysis()
        out["argument_bytes"] = int(ma.argument_size_in_bytes)
        out["output_bytes"] = int(ma.output_size_in_bytes)
        out["temp_bytes"] = int(ma.temp_size_in_bytes)
    except Exception:
        out["argument_bytes"] = out["output_bytes"] = out["temp_bytes"] = 0
    return out


def record_memory(
    registry: Optional[MetricsRegistry] = None,
) -> Dict[str, int]:
    """Set ``device_bytes_in_use`` / ``device_bytes_limit`` gauges per device.

    Returns ``{device_label: bytes_in_use}`` for the devices that report
    stats (CPU's ``memory_stats()`` is ``None`` — those are skipped, so on
    host-only runs this is an empty dict, not an error).
    """
    reg = metrics() if registry is None else registry
    seen: Dict[str, int] = {}
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:  # pragma: no cover - backend specific
            stats = None
        if not stats:
            continue
        label = f"{d.platform}:{d.id}"
        in_use = int(stats.get("bytes_in_use", 0))
        reg.gauge("device_bytes_in_use", device=label).set(in_use)
        limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
        if limit:
            reg.gauge("device_bytes_limit", device=label).set(int(limit))
        peak = stats.get("peak_bytes_in_use")
        if peak:
            reg.gauge("device_peak_bytes_in_use", device=label).set(int(peak))
        seen[label] = in_use
    return seen
