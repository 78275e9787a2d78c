"""Production training launcher.

Wires together: config system -> model -> sharded train step -> data
pipeline -> checkpointing (auto-resume, async, keep-N) -> straggler monitor.
Single-host it runs on whatever devices exist (CPU included); multi-host it
is the same code under ``jax.distributed.initialize`` (the mesh helper and
per-host data slicing are already process-count aware by construction).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch xlstm_125m --reduced \
      --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --ckpt-every 50
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config
from repro.data.tokens import TokenPipeline
from repro.dist.checkpoint import CheckpointManager
from repro.dist.sharding import is_axes_leaf, logical_to_sharding, set_mesh
from repro.dist.straggler import Action, StragglerMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models.model_zoo import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import (
    TrainConfig,
    init_train_state,
    make_train_step,
    state_axes,
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8", "topk"],
                    help="legacy in-graph compression of the already-"
                         "reduced grads (simulation only)")
    ap.add_argument("--dcn-compression", default="none",
                    choices=["none", "int8", "topk", "topk_ef"],
                    help="wire compression on the cross-pod (DCN) hop of "
                         "the hierarchical gradient reduction")
    ap.add_argument("--dcn-pods", type=int, default=0,
                    help="per-pod gradient slices; 0 = size of the mesh's "
                         "'pod' axis (1 when absent)")
    ap.add_argument("--dcn-topk-frac", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=0,
                    help="base of the per-step stochastic-rounding key")
    ap.add_argument("--imc-linear", action="store_true",
                    help="route FFN down-projections through the SpecPCM "
                         "IMC quantized-matmul model")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", default="debug",
                    choices=["debug", "single", "multi"])
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.imc_linear:
        import dataclasses
        cfg = dataclasses.replace(cfg, imc_linear=True)

    if args.mesh == "debug":
        mesh = make_debug_mesh()
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    set_mesh(mesh)
    print(f"mesh: {dict(mesh.shape)} devices={mesh.devices.size}")

    model = build_model(cfg)
    tcfg = TrainConfig(
        optimizer=AdamWConfig(lr=args.lr, total_steps=args.steps),
        remat=args.remat, microbatches=args.microbatches,
        grad_compression=args.grad_compression,
        dcn_compression=args.dcn_compression, dcn_pods=args.dcn_pods,
        dcn_topk_frac=args.dcn_topk_frac, seed=args.seed,
    )

    with mesh:
        state, axes = init_train_state(model, jax.random.PRNGKey(0),
                                       tcfg, mesh)
        st_axes = state_axes(axes, tcfg)
        state_sh = jax.tree.map(
            lambda ax, x: logical_to_sharding(ax, tuple(x.shape), mesh),
            st_axes, state, is_leaf=is_axes_leaf)
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, s) if s is not None else x,
            state, state_sh)
        raw_step = make_train_step(model, tcfg, mesh)
        step_fn = jax.jit(raw_step, donate_argnums=(0,))
        if raw_step.dcn_route != "global":
            print(f"grad sync: {raw_step.dcn_route} hierarchy over "
                  f"{raw_step.dcn_pods} pod(s), "
                  f"dcn_compression={tcfg.dcn_compression}")

        pipe = TokenPipeline(batch=args.batch, seq=args.seq,
                             vocab=cfg.vocab_size)

        start_step = 0
        ckpt = None
        if args.ckpt_dir:
            ckpt = CheckpointManager(args.ckpt_dir, keep=3)
            restored = ckpt.restore_latest(state, state_sh)
            if restored is not None:
                start_step, state = restored
                print(f"resumed from checkpoint step {start_step}")

        monitor = StragglerMonitor(
            on_warn=lambda s, dt: print(f"[straggler] step {s}: {dt:.3f}s"),
            on_evict=lambda s, dt: print(
                f"[straggler] step {s}: {dt:.3f}s — would evict+reshard"),
        )

        t_start = time.time()
        for step in range(start_step, args.steps):
            monitor.step_start()
            batch = pipe.get_for(cfg, step)
            state, metrics = step_fn(state, batch)
            action = monitor.step_end()
            if action == Action.EVICT and ckpt is not None:
                ckpt.save_async(step + 1, state)
            if (step + 1) % args.log_every == 0 or step == start_step:
                loss = float(metrics["loss"])
                gn = float(metrics["grad_norm"])
                dcn = ""
                if float(metrics["dcn_bytes"]) > 0:
                    dcn = (f" dcn={float(metrics['dcn_bytes']) / 2**20:.2f}"
                           f"MiB/pod ({float(metrics['dcn_raw_bytes']) / max(float(metrics['dcn_bytes']), 1.0):.1f}x"
                           " smaller)")
                print(f"step {step + 1}: loss={loss:.4f} grad_norm={gn:.3f} "
                      f"({(time.time() - t_start) / (step - start_step + 1):.2f}s/step)"
                      + dcn, flush=True)
            if ckpt is not None and (step + 1) % args.ckpt_every == 0:
                ckpt.save_async(step + 1, state)
        if ckpt is not None:
            ckpt.save(args.steps, state)
            ckpt.wait()
        print(f"done: {args.steps - start_step} steps in "
              f"{time.time() - t_start:.1f}s")
        return state


if __name__ == "__main__":
    main()
