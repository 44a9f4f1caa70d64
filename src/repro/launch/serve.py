"""Serving launcher: continuous-batched decode.

``python -m repro.launch.serve --arch qwen3-4b --preset reduced --requests 12``

One prefill lowering + one decode lowering serve the whole run. Slots are a
fixed-size batch; finished sequences (EOS or budget) are swapped for queued
requests by resetting that row's cache in place (functional cache, so this is
a cheap host-side gather/update). Reports tokens/s and per-phase timings.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, sharding_overrides
from repro.distributed.sharding import sharding_scope
from repro.launch.mesh import make_mesh
from repro.models.steps import make_decode_step, make_prefill_step
from repro.models.transformer import init_model


def cache_batch_axes(cfg):
    """Which axis of each cache leaf is the batch axis."""
    axes = {"len": 0}
    if cfg.family in ("dense", "moe", "encdec"):
        axes.update(k=1, v=1)
        if cfg.kv_quant:
            axes.update(k_scale=1, v_scale=1)
    if cfg.family == "encdec":
        axes.update(cross_k=1, cross_v=1)
    if cfg.family == "ssm":
        axes.update(conv=1, ssd=1)
    if cfg.family == "hybrid":
        axes.update(conv=2, ssd=2, k=1, v=1, tail_conv=1, tail_ssd=1)
    return axes


def _set_row(buf, row, b, axis):
    idx = [slice(None)] * buf.ndim
    idx[axis] = slice(b, b + 1)
    return buf.at[tuple(idx)].set(row)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--preset", choices=["reduced", "full"], default="reduced")
    ap.add_argument("--slots", type=int, default=4, help="batch slots")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.preset == "reduced":
        cfg = cfg.reduced()
    if cfg.family == "encdec" or cfg.frontend == "vision":
        raise SystemExit("serve demo targets decoder-only text archs")

    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    max_len = args.prompt_len + args.max_new
    rng = np.random.default_rng(args.seed)
    queue = [
        rng.integers(2, cfg.vocab_size, size=args.prompt_len).astype(np.int32)
        for _ in range(args.requests)
    ]

    with jax.set_mesh(mesh), sharding_scope(mesh, **sharding_overrides(cfg.name)):
        params = init_model(jax.random.PRNGKey(args.seed), cfg)
        prefill = jax.jit(make_prefill_step(cfg, max_len=max_len))
        decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

        B = args.slots
        t0 = time.perf_counter()
        prompts = np.stack([queue.pop(0) for _ in range(min(B, len(queue) + B))][:B]) \
            if len(queue) >= B else None
        if prompts is None:  # fewer requests than slots: pad with repeats
            rows = [queue.pop(0) if queue else np.zeros(args.prompt_len, np.int32)
                    for _ in range(B)]
            prompts = np.stack(rows)
        logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        remaining = [args.max_new] * B
        served = B
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        n_decoded = 0
        t0 = time.perf_counter()
        while True:
            logits, cache = decode(params, cache, tok)
            n_decoded += B
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            done = []
            for b in range(B):
                remaining[b] -= 1
                if remaining[b] <= 0:
                    done.append(b)
            if done and queue:
                # continuous batching: swap finished rows for queued requests
                for b in done:
                    if not queue:
                        break
                    prompt = queue.pop(0)
                    _, row_cache = prefill(
                        params, {"tokens": jnp.asarray(prompt[None])}
                    )
                    axes = cache_batch_axes(cfg)
                    cache = {
                        k: _set_row(cache[k], row_cache[k], b, axes[k])
                        for k in cache
                    }
                    remaining[b] = args.max_new
                    served += 1
            elif done and not queue:
                if all(r <= 0 for r in remaining):
                    break
            if n_decoded > (args.requests + B) * args.max_new * 2:
                break  # safety
        jax.block_until_ready(tok)
        t_decode = time.perf_counter() - t0

    print(f"[serve] {served} requests, {n_decoded} tokens decoded")
    print(f"[serve] prefill {t_prefill*1e3:.1f} ms; decode "
          f"{n_decoded / max(t_decode, 1e-9):.1f} tok/s "
          f"({t_decode*1e3/max(n_decoded,1):.2f} ms/tok)")
    return n_decoded


if __name__ == "__main__":
    main()
