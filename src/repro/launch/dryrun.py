import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, and record the roofline inputs.

For each cell this script:
  1. builds the step (train_step with optimizer, prefill_step, or decode_step),
  2. jits it with in/out shardings derived from the logical rules,
  3. ``.lower().compile()`` — a failure here (sharding mismatch, OOM at
     compile, unsupported collective) is a bug in the system,
  4. prints ``compiled.memory_analysis()`` (proves it fits) and
     ``cost_analysis()`` (FLOPs/bytes for §Roofline),
  5. parses the post-SPMD HLO for collective bytes,
  6. appends a JSON record consumed by benchmarks/roofline.py.

Usage:
  python -m repro.launch.dryrun --mesh single --arch all --shape all
  python -m repro.launch.dryrun --mesh multi  --arch gemma2-2b --shape train_4k
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, get_config, sharding_overrides
from repro.configs.deepwalk_web import CONFIG as DW_CONFIG
from repro.configs.shapes import (
    SHAPES,
    batch_logical_names,
    input_specs,
    shape_supported,
)
from repro.distributed.sharding import sharding_scope, tree_shardings
from repro.launch.mesh import make_production_mesh
from repro.models.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models.transformer import cache_specs, init_model, model_specs
from repro.train import optim

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
               "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
               "s16": 2, "u16": 2, "bf8": 1}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """bytes of an HLO shape string like 'bf16[16,512,128]{2,1,0}'."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str):
    """Sum per-collective operand/result bytes from post-SPMD HLO."""
    out = {c: 0 for c in COLLECTIVES}
    counts = {c: 0 for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.+?) (all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)", line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        out[op] += _shape_bytes(shape_str)
        counts[op] += 1
    return out, counts


def _avals(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def build_cell(arch: str, shape_name: str, mesh):
    """Returns (fn, args_avals, in_shardings, donate) for one cell."""
    shape = SHAPES[shape_name]
    if arch == DW_CONFIG.name:
        return build_graph_cell(shape, mesh)
    cfg = get_config(arch)
    ok, why = shape_supported(cfg, shape)
    if not ok:
        raise SkipCell(why)

    params_avals = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    p_specs = model_specs(cfg)
    params_sh = tree_shardings(params_avals, p_specs)

    if shape.kind == "train":
        opt = optim.make_optimizer(cfg.optimizer, 1e-4)
        opt_avals = jax.eval_shape(opt.init, params_avals)
        opt_specs = optim.optimizer_state_specs(cfg.optimizer, params_avals, p_specs)
        opt_sh = tree_shardings(opt_avals, opt_specs)
        (batch_avals,) = input_specs(cfg, shape)
        batch_sh = tree_shardings(batch_avals, batch_logical_names(cfg, train=True))
        step = make_train_step(cfg, opt, accum_steps=ACCUM_OVERRIDES.get(arch, 1))
        return (
            step,
            (params_avals, opt_avals, batch_avals),
            (params_sh, opt_sh, batch_sh),
            (0, 1),
        )

    if shape.kind == "prefill":
        (batch_avals,) = input_specs(cfg, shape)
        batch_sh = tree_shardings(batch_avals, batch_logical_names(cfg, train=False))
        step = make_prefill_step(cfg)
        return step, (params_avals, batch_avals), (params_sh, batch_sh), ()

    # decode
    cache_avals, tok_aval = input_specs(cfg, shape)
    cache_sh = tree_shardings(cache_avals, cache_specs(cfg))
    tok_sh = tree_shardings(tok_aval, ("batch", None))
    step = make_decode_step(cfg)
    return step, (params_avals, cache_avals, tok_aval), (params_sh, cache_sh, tok_sh), (1,)


class SkipCell(Exception):
    pass


# Microbatch gradient accumulation for the biggest trainers: shrinks remat
# carries and per-layer backward peaks by the accumulation factor (the
# standard grok-scale answer). One scan body either way — compile stays flat.
ACCUM_OVERRIDES = {"grok-1-314b": 4, "nemotron-4-15b": 2}


def build_graph_cell(shape, mesh):
    """The paper's own workload: sharded SGNS train step (deepwalk-web1b)."""
    from repro.skipgram.model import batch_loss

    c = DW_CONFIG
    V, D, K, B = c.n_nodes, c.dim, c.n_neg, c.global_batch
    pdt = jnp.dtype(c.param_dtype)
    params_avals = {
        "emb_in": jax.ShapeDtypeStruct((V, D), pdt),
        "emb_out": jax.ShapeDtypeStruct((V, D), pdt),
    }
    p_specs = {"emb_in": ("vocab", None), "emb_out": ("vocab", None)}
    params_sh = tree_shardings(params_avals, p_specs)
    opt = optim.adam(0.025)
    opt_avals = jax.eval_shape(opt.init, params_avals)
    opt_sh = tree_shardings(opt_avals, optim.adam_state_specs(p_specs))
    batch_avals = {
        "centers": jax.ShapeDtypeStruct((B,), jnp.int32),
        "contexts": jax.ShapeDtypeStruct((B,), jnp.int32),
        "negatives": jax.ShapeDtypeStruct((B, K), jnp.int32),
    }
    batch_sh = tree_shardings(
        batch_avals,
        {"centers": ("batch",), "contexts": ("batch",), "negatives": ("batch", None)},
    )

    def step(params, opt_state, batch):
        def loss_fn(p):
            return batch_loss(p, batch["centers"], batch["contexts"],
                              batch["negatives"], "ref")

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        return params, opt_state, loss

    return (
        step,
        (params_avals, opt_avals, batch_avals),
        (params_sh, opt_sh, batch_sh),
        (0, 1),
    )


def cell_overrides(arch: str, shape_name: str, model_axis: int = 16) -> dict:
    """Logical-rule overrides for one cell: per-arch + per-shape-kind."""
    overrides = sharding_overrides(arch)
    kind = SHAPES[shape_name].kind
    if kind == "train" and "res_seq" not in overrides:
        # sequence-parallel residual stream: bounds full-remat carries
        # (see distributed/sharding.py); train cells only
        overrides["res_seq"] = ("model",)
    if arch in REGISTRY and kind in ("decode", "prefill"):
        cfg = get_config(arch)
        if cfg.n_kv_heads % model_axis != 0 and "kv_seq" not in overrides:
            # KV heads can't shard the model axis: shard the cache's
            # sequence dim instead (flash-decode parallelism; GSPMD inserts
            # the partial-softmax all-reduce)
            overrides["kv_seq"] = ("model",)
    return overrides


def run_cell(arch: str, shape_name: str, multi_pod: bool, out):
    mesh_name = "multi" if multi_pod else "single"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        overrides = cell_overrides(arch, shape_name)
        with jax.set_mesh(mesh), sharding_scope(mesh, **overrides):
            fn, avals, in_sh, donate = build_cell(arch, shape_name, mesh)
            jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
            lowered = jitted.lower(*avals)
            compiled = lowered.compile()
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
        coll_bytes, coll_counts = parse_collective_bytes(hlo)
        rec.update(
            status="ok",
            compile_seconds=round(time.time() - t0, 2),
            memory=dict(
                argument_bytes=ma.argument_size_in_bytes,
                output_bytes=ma.output_size_in_bytes,
                temp_bytes=ma.temp_size_in_bytes,
                alias_bytes=ma.alias_size_in_bytes,
                code_bytes=ma.generated_code_size_in_bytes,
            ),
            flops=ca.get("flops", 0.0),
            bytes_accessed=ca.get("bytes accessed", 0.0),
            collective_bytes=coll_bytes,
            collective_counts=coll_counts,
        )
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
              f"({rec['compile_seconds']}s)")
        print(f"  memory_analysis: args={ma.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB "
              f"out={ma.output_size_in_bytes/2**30:.2f}GiB "
              f"aliased={ma.alias_size_in_bytes/2**30:.2f}GiB (per device)")
        print(f"  cost_analysis: flops={rec['flops']:.3e} "
              f"bytes={rec['bytes_accessed']:.3e} (per device)")
        print(f"  collectives: " + ", ".join(
            f"{k}={v/2**20:.1f}MiB(x{coll_counts[k]})"
            for k, v in coll_bytes.items() if v))
    except SkipCell as e:
        rec.update(status="skip", reason=str(e))
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: SKIP ({e})")
    except Exception as e:  # a failure here is a deliverable failure
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: FAIL {e}")
    out.append(rec)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--include-graph", action="store_true",
                    help="also dry-run the paper's deepwalk-web1b SGNS step")
    args = ap.parse_args()

    archs = sorted(REGISTRY) if args.arch == "all" else args.arch.split(",")
    if args.include_graph or args.arch == DW_CONFIG.name:
        if DW_CONFIG.name not in archs:
            archs.append(DW_CONFIG.name)
        if args.arch == DW_CONFIG.name:
            archs = [DW_CONFIG.name]
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    out = []
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                if arch == DW_CONFIG.name and shape != "train_4k":
                    continue  # graph workload has one canonical shape
                run_cell(arch, shape, multi, out)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    # merge with existing records (other shards may write too)
    existing = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            existing = json.load(f)
    key = lambda r: (r["arch"], r["shape"], r["mesh"])
    merged = {key(r): r for r in existing}
    merged.update({key(r): r for r in out})
    with open(args.out, "w") as f:
        json.dump(sorted(merged.values(), key=key), f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in out)
    n_skip = sum(r["status"] == "skip" for r in out)
    n_fail = sum(r["status"] == "fail" for r in out)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skip, {n_fail} fail -> {args.out}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
