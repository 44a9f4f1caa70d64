"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

Everything a cell needs is found by name from the benchmark's own files:

* ``BENCHMARK.json`` (at the root) names the cell's configuration and traffic
  mix, and the metrics it reports;
* the configuration's ``file`` (``configs/<name>.json``) holds its sizes;
* ``traffic/<traffic>.json`` holds the mix, whose ``kind`` names the
  driver, ``cells/<kind>.py``, which defines ``Cell`` and reads the mix;
* ``checks/<kind>.json`` holds the limit of every number compared;
* ``layer_metrics/<metric>.py`` reads one per-layer metric;
* ``peaks.json`` holds the device's peaks, keyed by ``device_kind``.

A run: set-up (graph, service, warm-up) counts as ``setup_s``; the window
lasts ``--seconds``; then device memory is read, the service is let go and
the plain reference decides ``correct``. ``--trace 1`` records the window
with the JAX profiler and the program's spans, and reports the per-layer
metrics in place of the end-to-end ones.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional


from . import trace_reduce
from .graphgen import make_graph
from .layer import LayerContext, load_reader

__all__ = ["Spec", "run", "main", "NoAccelerator"]

CHIP = Path("benchmarks") / "chip"


class NoAccelerator(RuntimeError):
    pass


class Spec:
    """One cell as ``BENCHMARK.json`` and the files it names describe it."""

    def __init__(self, root: Path, workload: str):
        self.root = Path(root)
        self.chip_dir = self.root / CHIP
        bench = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {c["name"]: c for c in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"cells: {sorted(cells)}")
        self.cell = cells[workload]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = json.loads(
            (self.root / configs[self.cell["config"]]["file"]).read_text())
        self.traffic = json.loads(
            (self.chip_dir / "traffic" / f"{self.cell['traffic']}.json")
            .read_text())
        self.kind = self.traffic["kind"]
        self.limits = json.loads(
            (self.chip_dir / "checks" / f"{self.kind}.json").read_text()
        )["limits"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)
        ]

    @property
    def name(self) -> str:
        return self.cell["name"]

    def driver(self, seed: int, annotate):
        """The cell's driver, ``cells/<kind>.Cell``, for one run."""
        path = self.chip_dir / "cells" / f"{self.kind}.py"
        if not re.fullmatch(r"[a-z][a-z0-9_]*", self.kind) \
                or not path.is_file():
            raise ValueError(f"no driver cells/{self.kind}.py for traffic "
                             f"kind {self.kind!r}")
        name = f"{__package__}.cells.{self.kind}"
        mod = sys.modules.get(name)
        if mod is None or Path(mod.__file__) != path:
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[name] = mod
            spec.loader.exec_module(mod)
        return mod.Cell(self.config, self.traffic, seed, annotate)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_devices(chips: int):
    """The devices to run on; raises when JAX finds no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoAccelerator(
            f"needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
    return devices[:chips]


class CompileLog:
    """Records, by name, the programs JAX traces, lowers and compiles, and
    its persistent-cache hits, so that any inside the window is reported."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        import jax

        self.events: List[tuple] = []  # (what, function, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **kw):
        if event in self.EVENTS:
            self.events.append((self.EVENTS[event], kw.get("fun_name", "?"),
                                float(duration)))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.events.append(("loaded from the persistent cache", "?", 0.0))

    def mark(self) -> int:
        return len(self.events)

    def report(self, since: int) -> str:
        new = self.events[since:]
        counts = {w: sum(1 for e in new if e[0] == w)
                  for w in (*self.EVENTS.values(),
                            "loaded from the persistent cache")}
        text = ", ".join(f"{w} {c}" for w, c in counts.items())
        slow = sorted((e for e in new if e[1] != "?"), key=lambda e: -e[2])
        if slow:
            text += "; longest: " + ", ".join(
                f"{w} {f} {t:.3f} s" for w, f, t in slow[:5])
        return text


def _peaks(chip_dir: Path, kind: str) -> Dict[str, float]:
    table = json.loads((chip_dir / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def _memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


@contextlib.contextmanager
def _profiled(enabled: bool):
    """The JAX profiler around the window, writing under TMPDIR."""
    if not enabled:
        yield None
        return
    import jax

    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def _layer_metrics(spec: Spec, ctx: LayerContext) -> Dict[str, dict]:
    out = {}
    for m in spec.per_layer:
        value = load_reader(spec.chip_dir, m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(spec: Spec, *, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True) -> dict:
    """One run of ``spec``'s cell; returns the result object."""
    import jax

    if require_tpu:
        devices = require_devices(int(spec.cell["chips"]))
    else:
        devices = jax.devices()[: int(spec.cell["chips"])]
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import trace as obs

    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {enable_compile_cache()}")
    compiles = CompileLog()
    annotate = jax.profiler.TraceAnnotation if trace else (
        lambda name: contextlib.nullcontext())
    cell = spec.driver(seed, annotate)
    graph = make_graph(spec.config)
    cell.setup(graph)

    tracer = obs.enable(exemplar_watch=()) if trace else None
    with _profiled(trace) as logdir:
        c0 = compiles.mark()
        t_window = time.perf_counter()
        setup_s = t_window - t_start
        with annotate("bench.window"):
            res = cell.window(seconds)
    if tracer is not None:
        obs.disable()
    log(f"set-up {setup_s:.3f} s; window {res['t1'] - res['t0']:.3f} s; "
        f"programs in the window: {compiles.report(c0)}")
    for note in res["notes"]:
        log(note)
    memory_peak = _memory_peak(devices)
    cell.collect()
    gc.collect()

    metrics: Dict[str, dict] = {}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if not trace:
        values = dict(res["e2e"], setup_s=setup_s)
        for m in spec.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        spans = [e for e in tracer.events
                 if res["t0"] <= e["ts"] <= res["t1"]]
        summary = _reduce_trace(logdir, spans, res)
        shutil.rmtree(logdir, ignore_errors=True)
        ctx = LayerContext(
            kind=spec.kind, window=(res["t0"], res["t1"]), spans=spans,
            counters=res["counters"], samples=res["samples"], trace=summary,
            peaks=(_peaks(spec.chip_dir, dev.device_kind)
                   if dev.platform == "tpu" else None),
        )
        metrics = _layer_metrics(spec, ctx)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ops = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])
        breakdown = {"device_ops": [[k, v] for k, v in ops[:10]],
                     "idle_gaps": [[k, v] for k, v in summary.gaps[:10]]}

    readings = cell.check()
    checks = {k: {"value": v, "limit": spec.limits[k]}
              for k, v in readings.items()}
    correct = all(v <= spec.limits[k] for k, v in readings.items())
    result = {"correct": bool(correct), "attempted": int(res["attempted"]),
              "failed": int(res["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _reduce_trace(logdir: str, spans: List[dict], res: dict):
    """Summary of the window, with the program's spans on the trace clock."""
    data = trace_reduce.load_xplane(trace_reduce.find_xplane(logdir))
    marks = [s for s in data.spans if s.name == "bench.window"]
    if not marks:
        raise RuntimeError("the trace holds no bench.window span")
    w = marks[-1]
    offset = w.start - res["t0"]  # perf_counter -> trace clock
    extra = [trace_reduce.Span(e["name"], e["ts"] + offset, e["dur"])
             for e in spans]
    return trace_reduce.summarize(data, w.start, w.end, spans=extra)


def print_result(result: dict) -> None:
    for k, c in result["checks"].items():
        log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)


def main(argv=None, *, t_start: Optional[float] = None,
         root: Optional[Path] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2] if root is None else root
    spec = Spec(root, args.workload)
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        log(f"the program's sources are missing next to the benchmark: {e}")
        return 2
    try:
        result = run(spec, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=t_start)
    except NoAccelerator as e:
        log(str(e))
        return 1
    print_result(result)
    return 0
