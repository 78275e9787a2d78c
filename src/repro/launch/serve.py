"""Batched serving launcher: prefill + decode loop with a KV/state cache.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch hymba_1_5b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.tokens import TokenPipeline
from repro.dist.sharding import set_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models.model_zoo import build_model
from repro.train.serve_step import make_decode_step, make_prefill


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_debug_mesh()
    set_mesh(mesh)
    model = build_model(cfg)

    with mesh:
        params, _ = model.init(jax.random.PRNGKey(0))
        pipe = TokenPipeline(batch=args.batch, seq=args.prompt_len,
                             vocab=cfg.vocab_size)
        batch = pipe.get_for(cfg, 0)
        max_len = args.prompt_len + args.gen
        cache = model.init_cache(args.batch, max_len)

        prefill = jax.jit(make_prefill(model))
        decode = jax.jit(make_decode_step(model))

        t0 = time.time()
        logits, cache = prefill(params, batch, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t_prefill = time.time() - t0

        out_tokens = [tok]
        pos0 = (batch["tokens"].shape[1]
                if cfg.family != "vlm"
                else batch["tokens"].shape[1] + batch["patches"].shape[1])
        t0 = time.time()
        key = jax.random.PRNGKey(1)
        for i in range(args.gen - 1):
            logits, cache = decode(params, tok, cache,
                                   jnp.asarray(pos0 + i, jnp.int32))
            if args.temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(
                    sub, logits[:, -1] / args.temperature)[:, None]
                tok = tok.astype(jnp.int32)
            else:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out_tokens.append(tok)
        gen = jnp.concatenate(out_tokens, axis=1)
        t_decode = time.time() - t0
        print(f"prefill: {t_prefill:.3f}s for {args.batch}x{args.prompt_len}")
        print(f"decode:  {t_decode:.3f}s for {args.gen - 1} steps "
              f"({1000 * t_decode / max(args.gen - 1, 1):.1f} ms/tok)")
        print("generated token ids (first row):", gen[0][:16].tolist())
        return gen


if __name__ == "__main__":
    main()
