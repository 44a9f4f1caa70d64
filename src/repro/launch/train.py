"""Training launcher: ``python -m repro.launch.train --arch qwen3-4b ...``

Production loop wiring on any device topology (1-CPU smoke to multi-pod):
mesh + logical sharding rules, jit'd train step (optional microbatch
accumulation + cross-pod gradient compression), synthetic-but-deterministic
data pipeline with prefetch, straggler monitor, hang watchdog, preemption
handler, and atomic checkpoints with auto-resume — every fault-tolerance
feature in DESIGN.md §6 is exercised by this driver.

CPU quickstart (reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --preset reduced \
      --steps 20 --batch 4 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, sharding_overrides
from repro.configs.shapes import batch_logical_names
from repro.data.pipeline import PrefetchIterator, SyntheticLMData
from repro.distributed.checkpoint import CheckpointManager
from repro.distributed.compression import ErrorFeedbackInt8
from repro.distributed.sharding import sharding_scope, tree_shardings
from repro.distributed.watchdog import HangWatchdog, StragglerMonitor
from repro.launch.mesh import make_mesh
from repro.models.steps import make_train_step
from repro.models.transformer import init_model, model_specs
from repro.train import optim


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", choices=["reduced", "full"], default="reduced")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--mesh", default="", help="e.g. 2x4 -> (data=2, model=4)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics", default="")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    cfg = get_config(args.arch)
    if args.preset == "reduced":
        cfg = cfg.reduced()

    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
    else:
        dims = (jax.device_count(), 1)
    mesh = make_mesh(dims, ("data", "model")[: len(dims)] if len(dims) == 2
                     else ("pod", "data", "model"))

    data = SyntheticLMData(cfg.vocab_size, args.batch, args.seq, seed=args.seed)
    opt = optim.make_optimizer(
        cfg.optimizer, optim.warmup_cosine(args.lr, 10, max(args.steps, 20))
    )

    compressor = ErrorFeedbackInt8() if args.compress_grads else None
    comp_state = {}

    def grad_transform(grads):
        if compressor is None:
            return grads
        out, comp_state["s"] = compressor.compress_decompress(
            grads, comp_state.get("s") or compressor.init(grads)
        )
        return out

    step_fn = make_train_step(cfg, opt, accum_steps=args.accum,
                              grad_transform=grad_transform if compressor else None)

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    stop = {"now": False}
    signal.signal(signal.SIGTERM, lambda *_: stop.update(now=True))

    with jax.set_mesh(mesh), sharding_scope(mesh, **sharding_overrides(cfg.name)):
        p_specs = model_specs(cfg)
        params_avals = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(args.seed), cfg))
        params_sh = tree_shardings(params_avals, p_specs)
        opt_avals = jax.eval_shape(opt.init, params_avals)
        opt_sh = tree_shardings(
            opt_avals, optim.optimizer_state_specs(cfg.optimizer, params_avals, p_specs)
        )
        batch_sh = tree_shardings(
            jax.eval_shape(lambda: jax.tree.map(jnp.asarray, data.batch_at(0))),
            batch_logical_names(cfg, train=True),
        )

        start_step = 0
        if mgr is not None and mgr.latest_step() is not None:
            start_step = mgr.latest_step()
            tree = mgr.restore(
                start_step,
                {"params": params_avals, "opt": opt_avals},
                {"params": params_sh, "opt": opt_sh},
            )
            params, opt_state = tree["params"], tree["opt"]
            print(f"[train] resumed from step {start_step}")
        else:
            params = init_model(jax.random.PRNGKey(args.seed), cfg)
            opt_state = opt.init(params)

        jit_step = jax.jit(
            step_fn, in_shardings=(params_sh, opt_sh, batch_sh), donate_argnums=(0, 1)
        )

        monitor = StragglerMonitor()
        metrics_path = args.metrics or (os.path.join(args.ckpt_dir, "metrics.jsonl")
                                        if args.ckpt_dir else "")
        mf = open(metrics_path, "a") if metrics_path else None

        def batches():
            s = start_step
            while True:
                yield s, data.batch_at(s)
                s += 1

        it = PrefetchIterator(batches(), depth=2)
        wd = HangWatchdog(600.0, lambda: print("[train] WATCHDOG: step hang"))
        losses = []
        for s, batch in it:
            if s >= args.steps or stop["now"]:
                break
            monitor.start_step()
            wd.arm()
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            loss = float(metrics["loss"])
            wd.disarm()
            slow = monitor.end_step()
            losses.append(loss)
            rec = {"step": s, "loss": loss, "straggler": slow,
                   "grad_norm": float(metrics["grad_norm"])}
            if mf:
                mf.write(json.dumps(rec) + "\n")
                mf.flush()
            if s % 5 == 0 or s == args.steps - 1:
                print(f"[train] step {s} loss {loss:.4f}"
                      + (" (straggler)" if slow else ""))
            if mgr is not None and (s + 1) % args.ckpt_every == 0:
                mgr.save(s + 1, {"params": params, "opt": opt_state}, blocking=False)
        if mgr is not None:
            mgr.wait()
            final = s if stop["now"] else args.steps
            mgr.save(final, {"params": params, "opt": opt_state})
            print(f"[train] checkpointed step {final}")
        if mf:
            mf.close()
        print(f"[train] done: first loss {losses[0]:.4f} last loss {losses[-1]:.4f} "
              f"stragglers {monitor.straggler_fraction:.2%}")
        return losses


if __name__ == "__main__":
    main()
