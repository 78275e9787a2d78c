import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("REPRO_XLA_EXTRA", ""))

"""Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
production meshes, proving the distribution config is coherent, and record
memory/cost/collective analyses for the roofline table.

MUST be run as its own process (the XLA_FLAGS line above executes before any
jax import — do not import this module from a live jax process).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_7b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.shapes import applicable
from repro.dist.sharding import logical_to_sharding, set_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    active_profile,
    model_flops_estimate,
    roofline_from_compiled,
)
from repro.models.model_zoo import build_model
from repro.train.serve_step import make_decode_step, make_prefill
from repro.train.train_step import (
    TrainConfig,
    abstract_train_state,
    make_train_step,
    state_axes,
)


def _leaf_axes(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None)))
                                        for e in x)


def _serve_cast(pshapes):
    """Serving deployments hold weights in bf16 (fp32 master copies live in
    the training job); reflect that in the serve-shape dry-runs."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
        if s.dtype == jnp.float32 else s, pshapes)


def batch_axes_for(cfg, specs: dict) -> dict:
    out = {}
    for k, v in specs.items():
        out[k] = ("batch",) + (None,) * (len(v.shape) - 1)
    return out


def cache_axes_for(cfg, cache):
    """Logical axes for a cache pytree (dispatch on node dataclass types)."""
    from repro.models.layers import KVCache, QuantKVCache
    from repro.models.recurrent import MambaState, MLSTMState, SLSTMState

    stacked = cfg.family != "ssm"

    def kv_axes(leaf):
        pre = ("layer",) if stacked and leaf.ndim == 5 else ()
        return pre + ("batch", "kv_seq", "kv_heads", None)

    def scale_axes(leaf):
        pre = ("layer",) if stacked and leaf.ndim == 4 else ()
        return pre + ("batch", "kv_seq", "kv_heads")

    def node_axes(node):
        if isinstance(node, QuantKVCache):
            return QuantKVCache(k=kv_axes(node.k), v=kv_axes(node.v),
                                k_scale=scale_axes(node.k_scale),
                                v_scale=scale_axes(node.v_scale))
        if isinstance(node, KVCache):
            return KVCache(k=kv_axes(node.k), v=kv_axes(node.v))
        if isinstance(node, MambaState):
            pre = ("layer",) if stacked and node.h.ndim == 4 else ()
            return MambaState(h=pre + ("batch", None, None))
        if isinstance(node, MLSTMState):
            pre = ("layer",) if stacked and node.C.ndim == 5 else ()
            return MLSTMState(C=pre + ("batch", "heads", None, None),
                              n=pre + ("batch", "heads", None))
        if isinstance(node, SLSTMState):
            pre = ("layer",) if stacked and node.c.ndim == 3 else ()
            return SLSTMState(c=pre + ("batch", None),
                              n=pre + ("batch", None))
        if isinstance(node, tuple):
            return tuple(node_axes(e) for e in node)
        if isinstance(node, list):
            return [node_axes(e) for e in node]
        # bare array (cross-attn kv): (L, B, S, KV, hd) or (B, S, KV, hd)
        pre = ("layer",) if stacked and node.ndim == 5 else ()
        return pre + ("batch", None, "kv_heads", None)

    def is_node(x):
        return isinstance(x, (KVCache, MambaState, MLSTMState, SLSTMState)) \
            or hasattr(x, "shape")

    if isinstance(cache, list):
        return [node_axes(c) for c in cache]
    return node_axes(cache)


def shardings_of(axes_tree, shapes_tree, mesh):
    return jax.tree.map(
        lambda ax, sh: logical_to_sharding(ax, tuple(sh.shape), mesh),
        axes_tree, shapes_tree, is_leaf=_leaf_axes)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             remat: str = "full", rules=None, cast_params: bool = False,
             kv_quant: bool = False, tag_suffix: str = "") -> dict:
    cfg = get_config(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant_int8=True)
    shape = SHAPES[shape_name]
    if not applicable(cfg.family, shape_name, cfg.supports_long_decode):
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "status": "skipped",
                  "reason": "long_500k requires sub-quadratic decode "
                            "(DESIGN.md §4); this arch is pure full-attention"}
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{arch}__{shape_name}__{mesh_kind}.json").write_text(
            json.dumps(result, indent=1))
        return result
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    set_mesh(mesh, rules)
    model = build_model(cfg)
    t0 = time.time()

    if shape.kind == "train":
        state, axes = abstract_train_state(model)
        st_axes = state_axes(axes)
        state_sh = shardings_of(st_axes, state, mesh)
        specs = model.input_specs(shape)
        b_axes = batch_axes_for(cfg, specs)
        batch_sh = shardings_of(b_axes, specs, mesh)
        step = make_train_step(model, TrainConfig(
            remat=remat, cast_params_bf16=cast_params))
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         donate_argnums=(0,))
        with mesh:
            lowered = jitted.lower(state, specs)
    elif shape.kind == "prefill":
        pshapes, axes = model.abstract_params()
        pshapes = _serve_cast(pshapes)
        param_sh = shardings_of(axes, pshapes, mesh)
        spec = model.input_specs(shape)
        bspecs, cspecs = spec["batch"], spec["cache"]
        b_axes = batch_axes_for(cfg, bspecs)
        batch_sh = shardings_of(b_axes, bspecs, mesh)
        c_axes = cache_axes_for(cfg, cspecs)
        cache_sh = shardings_of(c_axes, cspecs, mesh)
        fn = make_prefill(model)
        jitted = jax.jit(fn, in_shardings=(param_sh, batch_sh, cache_sh),
                         donate_argnums=(2,))
        with mesh:
            lowered = jitted.lower(pshapes, bspecs, cspecs)
    else:  # decode
        pshapes, axes = model.abstract_params()
        pshapes = _serve_cast(pshapes)
        param_sh = shardings_of(axes, pshapes, mesh)
        spec = model.input_specs(shape)
        tok, cspecs, pos = spec["token"], spec["cache"], spec["pos"]
        tok_sh = logical_to_sharding(("batch", None), tuple(tok.shape), mesh)
        c_axes = cache_axes_for(cfg, cspecs)
        cache_sh = shardings_of(c_axes, cspecs, mesh)
        fn = make_decode_step(model)
        jitted = jax.jit(fn, in_shardings=(param_sh, tok_sh, cache_sh, None),
                         donate_argnums=(2,))
        with mesh:
            lowered = jitted.lower(pshapes, tok, cspecs, pos)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
            if hasattr(ma, field):
                mem[field] = int(getattr(ma, field))
        print("memory_analysis:", mem)
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}

    mf = model_flops_estimate(cfg, shape)
    prof = active_profile()
    roof = roofline_from_compiled(compiled, chips, model_flops=mf,
                                  profile=prof)
    print("cost_analysis: flops/chip=%.3e bytes/chip=%.3e coll/chip=%.3e "
          "(ceilings: %s)"
          % (roof.flops, roof.hbm_bytes, roof.coll_bytes, prof.source))

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok", "chips": chips,
        "variant": {"cast_params": cast_params, "kv_quant": kv_quant,
                    "remat": remat},
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "roofline": roof.to_dict(),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}__{shape_name}__{mesh_kind}{tag_suffix}"
    (out_dir / f"{tag}.json").write_text(json.dumps(result, indent=1))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--cast-params", action="store_true",
                    help="bf16 cast before FSDP all-gather (perf variant)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (perf variant)")
    ap.add_argument("--kv-seq-shard", action="store_true",
                    help="stripe KV cache seq axis over the model axis")
    ap.add_argument("--rules", default="default",
                    help="sharding rule preset (default | fsdp_only)")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.dist.sharding import RULE_PRESETS
    rules = RULE_PRESETS[args.rules]
    if args.kv_seq_shard:
        rules = rules.replace(kv_seq="model")

    out = Path(args.out)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    failures = 0
    for a, s, m in cells:
        tag = f"{a}__{s}__{m}"
        if args.skip_existing and (out / f"{tag}.json").exists():
            print(f"[skip-existing] {tag}")
            continue
        print(f"=== {tag} ===", flush=True)
        try:
            r = run_cell(a, s, m, out, remat=args.remat, rules=rules,
                         cast_params=args.cast_params,
                         kv_quant=args.kv_quant, tag_suffix=args.tag)
            print(f"[{r['status']}] {tag} "
                  + (f"compile={r.get('compile_s')}s "
                     f"bottleneck={r['roofline']['bottleneck']}"
                     if r["status"] == "ok" else r.get("reason", "")),
                  flush=True)
        except Exception:
            failures += 1
            err = traceback.format_exc()
            print(f"[FAIL] {tag}\n{err}", flush=True)
            out.mkdir(parents=True, exist_ok=True)
            (out / f"{tag}.json").write_text(json.dumps(
                {"arch": a, "shape": s, "mesh": m, "status": "fail",
                 "error": err.splitlines()[-1]}, indent=1))
    print(f"done: {len(cells)} cells, {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
