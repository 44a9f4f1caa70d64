"""Profile SGNS training at the refresh cell's shapes, on a TPU.

Builds the graph of ``benchmarks/chip/configs/graph500-s14-corewalk.json``,
takes its k-core nearest the cell's k0-core in size (~1,465 nodes), runs the
cell's CoreWalk walks on it (r 10, l 80, padded to 1,024-walk buckets) and
trains with the cell's SGNS settings (d 128, window 10, 5 negatives, batches
of 4,096, Adam at 0.025): ``train_sgns`` for ``--steps`` steps to compile,
again on the host clock, and once more under the JAX profiler. Prints
microseconds per step and the step program's device self time split by what
each HLO instruction holds: row scatters, dots, gathers, the
``searchsorted`` loop, the Pallas kernels, Adam's table passes, the rest.

Only the process that holds the chip can trace it, so run it there::

    python scripts/profile_sgns_step.py --out <dir>

It also runs against an older tree whose trainer has no core-local tables
(from that tree's root, with its ``src`` first on the path), which gives
the split before a change.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.getcwd())

from benchmarks.chip.graphgen import make_graph  # noqa: E402
from benchmarks.chip import trace_reduce  # noqa: E402
from repro.core.corewalk import corewalk_plan, plan_from_counts  # noqa: E402
from repro.core.kcore import core_numbers_host, kcore_subgraph  # noqa: E402
from repro.graph.csr import Graph  # noqa: E402
from repro.skipgram import trainer  # noqa: E402
from repro.skipgram.corpus import build_corpus  # noqa: E402
from repro.skipgram.model import init_params  # noqa: E402

CONFIG = "benchmarks/chip/configs/graph500-s14-corewalk.json"
CORE_SIZE = 1465  # the cell's k0-core, ~1,460-1,470 nodes


def corpus_and_cfg(seed: int):
    cfg = json.load(open(CONFIG))
    r = cfg["refresh"]
    n, edges = make_graph(cfg)
    g = Graph.from_edges(n, edges)
    core = core_numbers_host(g)
    k0 = min(range(1, int(core.max()) + 1),
             key=lambda k: abs(int((core >= k).sum()) - CORE_SIZE))
    budgets = np.where(core >= k0, corewalk_plan(core, r["n_walks"]).per_node,
                       0)
    wplan = plan_from_counts(budgets, 1024)
    sub = kcore_subgraph(g, core, k0)
    deg = sub.degrees()
    width = 1 << (int(deg.max()) - 1).bit_length()
    corpus = build_corpus(sub.to_ell(width=width), wplan, r["walk_length"],
                          jax.random.PRNGKey(seed))
    sgns = trainer.SGNSConfig(dim=cfg["dim"], window=r["window"],
                              n_neg=r["n_neg"], batch=r["sgns_batch"],
                              lr=r["lr"], seed=seed)
    info = {"nodes": n, "k0": k0, "core": int((core >= k0).sum()),
            "walks": list(corpus.walks.shape), "real_walks": wplan.n_real}
    return corpus, sgns, info


def warm_params(n: int, dim: int, seed: int):
    return init_params(n, dim, jax.random.PRNGKey(seed + 1))


# ------------------------------------------------------------ HLO reading

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INST = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")


def parse_hlo(text: str):
    """-> (instruction -> (computation, opcode, line), computation -> its
    instructions)."""
    inst, comps, cur = {}, defaultdict(list), None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and not line[0].isspace():  # headers start the line
            cur = m.group(1)
            continue
        m = _INST.match(line)
        if m and cur is not None:
            inst[m.group(1)] = (cur, m.group(2), line)
            comps[cur].append(m.group(1))
    return inst, comps


def _holds(name, inst, comps, seen=None):
    """Opcodes inside instruction ``name``, through the computations it
    calls."""
    seen = set() if seen is None else seen
    _, op, line = inst[name]
    out = {op}
    if "tpu_custom_call" in line:
        out.add("pallas")
    for c in _CALLS.findall(line):
        if c in seen:
            continue
        seen.add(c)
        for i in comps.get(c, ()):
            out |= _holds(i, inst, comps, seen)
    return out


def classify(text: str):
    """instruction -> category, for every instruction of the module."""
    inst, comps = parse_hlo(text)
    in_loop = set()
    for name, (_, op, line) in inst.items():
        if op == "while":
            stack = _CALLS.findall(line)
            while stack:
                c = stack.pop()
                if c in in_loop:
                    continue
                in_loop.add(c)
                for i in comps.get(c, ()):
                    stack += _CALLS.findall(inst[i][2])
    cat = {}
    for name, (comp, op, line) in inst.items():
        held = _holds(name, inst, comps)
        if op == "while" or comp in in_loop:
            cat[name] = "searchsorted loop"
        elif "pallas" in held:
            cat[name] = "pallas"
        elif "scatter" in held:
            cat[name] = "scatter"
        elif held & {"dot", "convolution"}:
            cat[name] = "dot"
        elif "gather" in held:
            cat[name] = "gather"
        elif held & {"sqrt", "rsqrt"}:
            cat[name] = "adam"
        else:
            cat[name] = "other"
    return cat


def step_hlo(corpus, cfg, rows: int) -> str:
    """The compiled HLO of the step ``train_sgns`` ran, lowered again on its
    argument shapes: tables of ``rows`` rows, the corpus's walks."""
    adam = trainer._adam(cfg.lr)
    f32 = jnp.float32
    tables = {k: jax.ShapeDtypeStruct((rows, cfg.dim), f32)
              for k in ("emb_in", "emb_out")}
    key = jax.eval_shape(lambda: jax.random.fold_in(jax.random.PRNGKey(0), 0))
    data = (jax.ShapeDtypeStruct(corpus.walks.shape, corpus.walks.dtype),
            corpus.n_real, jax.ShapeDtypeStruct((rows,), f32))
    return trainer._train_step.lower(
        tables, jax.eval_shape(adam.init, tables), data, key, impl=cfg.impl,
        window=cfg.window, n_neg=cfg.n_neg, batch=cfg.batch,
        opt_update=adam.update).compile().as_text()


def _inst_of(text: str) -> str:
    m = re.match(r"^%?([\w.\-]+)\s*=", text)
    return m.group(1) if m else text.split(" ", 1)[0]


def profile(out: str, steps: int, seed: int) -> dict:
    corpus, cfg, info = corpus_and_cfg(seed)
    n = corpus.n_nodes

    def run():
        res = trainer.train_sgns(corpus, cfg, params=warm_params(n, cfg.dim,
                                                                 seed),
                                 steps=steps)
        jax.block_until_ready(res.params)
        return res

    run()  # compiles
    t0 = time.perf_counter()
    res = run()
    us = (time.perf_counter() - t0) / steps * 1e6
    logdir = os.path.join(out, "trace")
    with jax.profiler.trace(logdir):
        run()
    rows = getattr(res, "rows", n)  # an older trainer trains all rows
    text = step_hlo(corpus, cfg, rows)
    with open(os.path.join(out, "train_step.hlo.txt"), "w") as f:
        f.write(text)
    cat = classify(text)
    data = trace_reduce.load_xplane(trace_reduce.find_xplane(logdir))
    ops, mods = data.ops[0], data.modules[0]
    step_mods = [m for m in mods if "train_step" in m.name]
    spans = [(m.start, m.start + m.dur) for m in step_mods]
    split, per_inst = defaultdict(float), defaultdict(float)
    for o, own in zip(ops, trace_reduce.self_seconds(ops)):
        if not any(a <= o.start <= b for a, b in spans):
            continue
        name = _inst_of(o.name)
        split[cat.get(name, "other")] += own
        per_inst[name] += own
    k = len(step_mods) or 1
    result = {
        "corpus": info,
        "rows": rows,
        "host_us_per_step": us,
        "programs_traced": len(step_mods),
        "device_us_per_step": {c: v / k * 1e6 for c, v in
                               sorted(split.items(), key=lambda t: -t[1])},
        "device_us_per_step_total": sum(split.values()) / k * 1e6,
        "top_instructions_us": {
            f"{i} [{cat.get(i, 'other')}]": v / k * 1e6 for i, v in
            sorted(per_inst.items(), key=lambda t: -t[1])[:15]},
        "device": jax.devices()[0].device_kind,
    }
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seed", type=int, default=2147483731)
    ap.add_argument("--label", default="")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("no TPU: these numbers come only from the chip")
    os.makedirs(a.out, exist_ok=True)
    res = {"label": a.label, "profile": profile(a.out, a.steps, a.seed)}
    with open(os.path.join(a.out, "result.json"), "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
