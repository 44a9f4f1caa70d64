import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# ^ must precede jax import: the calibration lowers on the production mesh.

"""§Roofline: three-term analysis per (arch x shape) from the compiled dry-run.

    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory term     = HLO_bytes / HBM_bw               (per chip)
    collective term = collective_bytes / link_bw       (per chip)

cost_analysis() and the parsed HLO are the per-chip SPMD module, so no /chips
is needed. TWO corrections applied and documented:

1. **Depth calibration** — XLA cost analysis counts a scanned layer body ONCE
   (while-loop trip counts are invisible to it). Each cell is re-lowered at
   two reduced depths with scan_layers=False; the per-layer delta
   extrapolates to full depth:   total = m(d2) + (L - d2) * (m(d4)-m(d2))/2.
2. **bf16 legalisation** — XLA *CPU* upcasts bf16 dots/buffers to f32, so
   HLO byte counts are inflated vs the TPU target (native bf16). Bytes are
   reported as-parsed (upper bound) with the caveat in EXPERIMENTS.md.

MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = matmul-participating
params (active experts only for MoE) + analytic attention/SSD term; the ratio
MODEL_FLOPS / HLO_FLOPs exposes remat/capacity/causal-padding waste.

Run:  PYTHONPATH=src python -m benchmarks.roofline --dryrun results/dryrun.json
"""
import argparse
import dataclasses
import json
import time

import jax
import numpy as np

# v5e hardware constants (assignment brief)
PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s
LINK_BW = 50e9  # bytes/s per ICI link

KINDS = {"train_4k": "train", "prefill_32k": "prefill",
         "decode_32k": "decode", "long_500k": "decode"}


# ------------------------------------------------------- analytic flops ----


def _param_count(cfg):
    from repro.models.transformer import init_model

    avals = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(avals))
    embed = int(np.prod(avals["embed"]["embedding"].shape))
    return total, embed, avals


def analytic_model_flops(cfg, shape):
    """MODEL_FLOPS for the whole step, per chip (/512 single-pod=256... the
    dry-run modules are per-chip; divide global by mesh size at the caller)."""
    from repro.configs.shapes import SHAPES

    sh = SHAPES[shape]
    B, S = sh.global_batch, sh.seq_len
    total, embed, _ = _param_count(cfg)
    n_mat = total - embed  # gather-only table
    if cfg.tie_embeddings:
        n_mat += embed  # tied table re-used as the unembed matmul
    if cfg.moe is not None:
        # experts: only top_k of n_experts are "useful" per token
        expert = cfg.n_layers * cfg.moe.n_experts * 3 * cfg.d_model * cfg.moe.d_ff_expert
        n_mat = n_mat - expert + expert * cfg.moe.top_k / cfg.moe.n_experts
    tokens = B * S if sh.kind != "decode" else B
    mult = 6 if sh.kind == "train" else 2
    flops = mult * n_mat * tokens

    # attention context term (causal-halved); decode reads the whole cache
    if cfg.family in ("dense", "moe", "encdec"):
        Hd = cfg.n_heads * cfg.head_dim
        if sh.kind == "decode":
            flops += cfg.n_layers * 4 * B * S * Hd
        else:
            ctx = S if not cfg.sliding_window else min(S, cfg.sliding_window)
            att = cfg.n_layers * 4 * B * S * ctx * Hd * 0.5
            flops += att * (3 if sh.kind == "train" else 1)
    elif cfg.ssm is not None:
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        n_ssm_layers = cfg.n_layers
        # SSD state math ~ 6 * tokens * d_inner * d_state per layer (fwd)
        ssd = n_ssm_layers * 6 * tokens * di * s.d_state
        flops += ssd * (3 if sh.kind == "train" else 1)
    return flops


# ------------------------------------------------------ depth calibration ---


def _variant_depths(cfg):
    if cfg.family == "hybrid":
        per = cfg.shared_every
        return (per, 2 * per)  # 1 group vs 2 groups
    return (1, 2)


def calibrate_cell(arch, shape_name):
    """Lower reduced-depth unrolled variants; return per-depth metrics."""
    from repro.configs import get_config, sharding_overrides
    from repro.configs.shapes import SHAPES
    from repro.distributed.sharding import sharding_scope
    from repro.launch import dryrun as dr
    from repro.launch.mesh import make_production_mesh

    cfg0 = get_config(arch)
    depths = _variant_depths(cfg0)
    mesh = make_production_mesh(multi_pod=False)
    out = {}
    S = 1 << 22  # "single chunk" sentinel: min(chunk, S) applies downstream
    for d in depths:
        # encoder depth tracks decoder depth so the per-layer delta covers an
        # (enc, dec) layer PAIR — valid for seamless where both stacks are 24.
        # Inner loops are de-scanned too (XLA cost analysis cannot see scan
        # trip counts): attention/loss run single-chunk (compile-only, so the
        # dense score/logit buffers are never allocated) and SSD's chunk scan
        # unrolls via scan_layers=False.
        cfg = dataclasses.replace(
            cfg0, n_layers=d, scan_layers=False,
            n_encoder_layers=min(cfg0.n_encoder_layers, d),
            attn_chunk_q=S, attn_chunk_kv=S, loss_chunk=S,
        )
        ov = dr.cell_overrides(arch, shape_name)
        with jax.set_mesh(mesh), sharding_scope(mesh, **ov):
            # patch the registry-free path: build_cell reads get_config, so
            # construct the cell manually with the variant cfg
            fn, avals, in_sh, donate = _build_variant(cfg, shape_name)
            compiled = (
                jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
                .lower(*avals)
                .compile()
            )
            ca = compiled.cost_analysis() or {}
            coll, _ = dr.parse_collective_bytes(compiled.as_text())
        out[d] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(sum(coll.values())),
        }
    d2, d4 = depths
    per_layer = {k: (out[d4][k] - out[d2][k]) / (d4 - d2) for k in out[d2]}
    base = {k: out[d2][k] - d2 * per_layer[k] for k in out[d2]}
    return per_layer, base, depths


def _build_variant(cfg, shape_name):
    """dryrun.build_cell but with an explicit (depth-reduced) cfg."""
    from repro.configs.shapes import (
        SHAPES, batch_logical_names, input_specs, shape_supported,
    )
    from repro.distributed.sharding import tree_shardings
    from repro.models.steps import make_decode_step, make_prefill_step, make_train_step
    from repro.models.transformer import cache_specs, init_model, model_specs
    from repro.train import optim

    shape = SHAPES[shape_name]
    params_avals = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    p_specs = model_specs(cfg)
    params_sh = tree_shardings(params_avals, p_specs)
    if shape.kind == "train":
        opt = optim.make_optimizer(cfg.optimizer, 1e-4)
        opt_avals = jax.eval_shape(opt.init, params_avals)
        opt_sh = tree_shardings(
            opt_avals, optim.optimizer_state_specs(cfg.optimizer, params_avals, p_specs)
        )
        (batch_avals,) = input_specs(cfg, shape)
        batch_sh = tree_shardings(batch_avals, batch_logical_names(cfg, train=True))
        # accum=1 for calibration: the microbatch loop is a scan (invisible
        # trip count); per-step totals are accumulation-invariant anyway.
        step = make_train_step(cfg, opt, accum_steps=1)
        return step, (params_avals, opt_avals, batch_avals), (params_sh, opt_sh, batch_sh), (0, 1)
    if shape.kind == "prefill":
        (batch_avals,) = input_specs(cfg, shape)
        batch_sh = tree_shardings(batch_avals, batch_logical_names(cfg, train=False))
        return make_prefill_step(cfg), (params_avals, batch_avals), (params_sh, batch_sh), ()
    cache_avals, tok_aval = input_specs(cfg, shape)
    cache_sh = tree_shardings(cache_avals, cache_specs(cfg))
    tok_sh = tree_shardings(tok_aval, ("batch", None))
    return (
        make_decode_step(cfg),
        (params_avals, cache_avals, tok_aval),
        (params_sh, cache_sh, tok_sh),
        (1,),
    )


def full_depth_units(cfg):
    """How many per-layer units the full model has. Hybrid depths are
    expressed in n_layers (mamba blocks) too — the per-unit delta from the
    (per, 2*per) variants is already per *block* (incl. its 1/shared_every
    share of the shared attention block)."""
    return cfg.n_layers


# ------------------------------------------------------------------ main ----


def suggest(dom, kind, cfg):
    if dom == "collective":
        return ("shrink cross-shard traffic: reshard to cut the SP gathers "
                "(bigger per-device batch) or overlap collectives with the "
                "next microbatch's compute")
    if dom == "memory":
        if kind == "decode":
            return ("decode is KV/state-bandwidth bound: quantise the cache "
                    "(int8 KV), shard it wider, or batch more requests per "
                    "cache pass")
        return "raise arithmetic intensity: larger fused blocks, bf16 end-to-end"
    return ("compute-bound (good): push MXU utilisation via Pallas-fused "
            "attention and capacity-factor reduction" if cfg.moe else
            "compute-bound (good): push MXU utilisation via Pallas-fused "
            "attention / larger matmul tiles")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="results/dryrun.json")
    ap.add_argument("--out", default="results/roofline.json")
    ap.add_argument("--md", default="results/roofline.md")
    ap.add_argument("--calibrate", action="store_true", default=True)
    ap.add_argument("--no-calibrate", dest="calibrate", action="store_false")
    ap.add_argument("--cells", default="", help="arch:shape,... subset filter")
    args = ap.parse_args()

    from repro.configs import REGISTRY, get_config

    with open(args.dryrun) as f:
        records = json.load(f)
    cells = [r for r in records if r["mesh"] == "single" and r["status"] == "ok"
             and r["arch"] in REGISTRY]
    if args.cells:
        keep = {tuple(c.split(":")) for c in args.cells.split(",")}
        cells = [r for r in cells if (r["arch"], r["shape"]) in keep]

    rows = []
    for r in cells:
        arch, shape = r["arch"], r["shape"]
        cfg = get_config(arch)
        flops = r["flops"]
        byts = r["bytes_accessed"]
        coll = float(sum(r["collective_bytes"].values()))
        corrected = False
        if args.calibrate:
            try:
                t0 = time.time()
                per_layer, base, depths = calibrate_cell(arch, shape)
                L = full_depth_units(cfg)
                flops = base["flops"] + per_layer["flops"] * L
                byts = base["bytes"] + per_layer["bytes"] * L
                coll = base["coll"] + per_layer["coll"] * L
                corrected = True
                print(f"[roofline] calibrated {arch}x{shape} at depths {depths} "
                      f"({time.time()-t0:.0f}s)")
            except Exception as e:  # fall back to raw (underestimates depth)
                print(f"[roofline] calibration FAILED {arch}x{shape}: {e}")
        t_c = flops / PEAK_FLOPS
        t_m = byts / HBM_BW
        t_n = coll / LINK_BW
        dom = max((("compute", t_c), ("memory", t_m), ("collective", t_n)),
                  key=lambda kv: kv[1])[0]
        model_flops = analytic_model_flops(cfg, shape) / 256  # per chip
        rows.append({
            "arch": arch, "shape": shape,
            "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
            "dominant": dom,
            "model_flops_per_chip": model_flops,
            "hlo_flops_per_chip": flops,
            "useful_ratio": model_flops / flops if flops else 0.0,
            "roofline_fraction": t_c / max(t_c, t_m, t_n),
            "calibrated": corrected,
            "suggestion": suggest(dom, KINDS.get(shape, "train"), cfg),
        })
        print(f"[roofline] {arch:22s} {shape:12s} compute {t_c*1e3:9.3f}ms "
              f"memory {t_m*1e3:9.3f}ms collective {t_n*1e3:9.3f}ms "
              f"-> {dom:10s} useful={rows[-1]['useful_ratio']:.2f}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)

    skips = [r for r in records if r["mesh"] == "single" and r["status"] == "skip"]
    with open(args.md, "w") as f:
        f.write("| arch | shape | compute (ms) | memory (ms) | collective (ms) "
                "| dominant | MODEL/HLO flops | roofline frac |\n|---|---|---|---|---|---|---|---|\n")
        for r in rows:
            f.write(
                f"| {r['arch']} | {r['shape']} | {r['compute_s']*1e3:.3f} | "
                f"{r['memory_s']*1e3:.3f} | {r['collective_s']*1e3:.3f} | "
                f"{r['dominant']} | {r['useful_ratio']:.2f} | "
                f"{r['roofline_fraction']:.2f} |\n")
        for r in skips:
            f.write(f"| {r['arch']} | {r['shape']} | — | — | — | skipped: "
                    f"{r['reason'][:60]} | — | — |\n")
    print(f"[roofline] wrote {args.out} and {args.md} "
          f"({len(rows)} cells, {len(skips)} skips)")


if __name__ == "__main__":
    main()
