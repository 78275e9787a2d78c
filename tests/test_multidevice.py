"""Multi-device tests via subprocess (8 host devices): the dry-run machinery
on a small mesh, sharded training equivalence, and compressed cross-pod
all-reduce. Subprocesses are used because device count is fixed at jax init.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run_py(code: str, devices: int = 8, timeout: int = 520):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)


@pytest.mark.slow
def test_dryrun_machinery_small_mesh(tmp_path):
    """Exercise run_cell end-to-end on an 8-device (2, 4) mesh by shrinking
    the production mesh — proves lower/compile/analysis plumbing without the
    512-device cost."""
    r = _run_py(f"""
        from pathlib import Path
        import repro.launch.mesh as mesh_mod
        from repro.launch.mesh import make_mesh
        mesh_mod.make_production_mesh = (
            lambda multi_pod=False: make_mesh((2, 2, 2), ("pod", "data", "model"))
            if multi_pod else make_mesh((2, 4), ("data", "model")))
        import repro.launch.dryrun as dr
        import repro.configs.shapes as shp
        import dataclasses
        shp.SHAPES["train_4k"] = dataclasses.replace(
            shp.SHAPES["train_4k"], global_batch=8, seq_len=256)
        out = dr.run_cell("qwen2_7b", "train_4k", "single",
                          Path(r"{tmp_path}"))
        assert out["status"] == "ok", out
        assert out["roofline"]["flops"] > 0
        out2 = dr.run_cell("qwen2_7b", "train_4k", "multi",
                           Path(r"{tmp_path}"))
        assert out2["status"] == "ok", out2
        print("DRYRUN_OK")
    """)
    assert "DRYRUN_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_sharded_training_matches_single_device():
    """The same train step on a (2, 2, 2) mesh and on a host replica must
    produce identical losses (SPMD correctness)."""
    r = _run_py("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.data.tokens import TokenPipeline
        from repro.dist.sharding import set_mesh, logical_to_sharding
        from repro.launch.mesh import make_mesh
        from repro.train.train_step import (TrainConfig, init_train_state,
                                            make_train_step, state_axes)

        cfg = get_config("qwen2_7b").reduced()
        model = build_model(cfg)
        pipe = TokenPipeline(batch=8, seq=32, vocab=cfg.vocab_size)
        losses = {}
        for mode in ("replicated", "sharded"):
            if mode == "sharded":
                mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
                set_mesh(mesh)
            else:
                set_mesh(None)
            state, axes = init_train_state(model, jax.random.PRNGKey(0))
            if mode == "sharded":
                st_axes = state_axes(axes)
                sh = jax.tree.map(
                    lambda ax, x: logical_to_sharding(ax, tuple(x.shape), mesh),
                    st_axes, state,
                    is_leaf=lambda x: isinstance(x, tuple) and all(
                        isinstance(e, (str, type(None))) for e in x))
                state = jax.tree.map(
                    lambda x, s: jax.device_put(x, s) if s is not None else x,
                    state, sh)
            step = jax.jit(make_train_step(model, TrainConfig()))
            ls = []
            for s in range(3):
                state, m = step(state, pipe.get_for(cfg, s))
                ls.append(float(m["loss"]))
            losses[mode] = ls
        np.testing.assert_allclose(losses["replicated"], losses["sharded"],
                                   rtol=1e-4)
        print("SPMD_OK", losses["sharded"])
    """)
    assert "SPMD_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_hierarchical_train_step_on_pod_mesh():
    """The real shard_map route of the hierarchical ICI/DCN train step on
    a (2, 2, 2) ('pod', 'data', 'model') mesh: with dcn_compression='none'
    it matches the single-device emulated fold (SPMD correctness), and
    topk_ef trains with pod-sharded EF residuals."""
    r = _run_py("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.data.tokens import TokenPipeline
        from repro.dist.sharding import (set_mesh, is_axes_leaf,
                                         logical_to_sharding)
        from repro.launch.mesh import make_mesh
        from repro.train.train_step import (TrainConfig, init_train_state,
                                            make_train_step, state_axes)

        cfg = get_config("qwen2_7b").reduced()
        model = build_model(cfg)
        pipe = TokenPipeline(batch=8, seq=32, vocab=cfg.vocab_size)
        from repro.train.optimizer import AdamWConfig
        opt = AdamWConfig(lr=1e-3)

        def run(tcfg, mesh):
            set_mesh(mesh)
            state, axes = init_train_state(model, jax.random.PRNGKey(0),
                                           tcfg, mesh)
            if mesh is not None:
                sh = jax.tree.map(
                    lambda ax, x: logical_to_sharding(ax, tuple(x.shape), mesh),
                    state_axes(axes, tcfg), state, is_leaf=is_axes_leaf)
                state = jax.tree.map(
                    lambda x, s: jax.device_put(x, s) if s is not None else x,
                    state, sh)
            raw = make_train_step(model, tcfg, mesh)
            fn = jax.jit(raw)
            ls = []
            for s in range(3):
                state, m = fn(state, pipe.get_for(cfg, s))
                ls.append(float(m["loss"]))
            set_mesh(None)
            return raw, state, ls

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

        # defaults on a pod mesh keep the pre-hierarchy global reduction
        # (an uncompressed shard_map hop would cost memory for nothing)
        raw_g = make_train_step(model, TrainConfig(optimizer=opt), mesh)
        assert raw_g.dcn_route == "global", raw_g.dcn_route

        raw_e, _, l_emulated = run(TrainConfig(optimizer=opt, dcn_pods=2),
                                   None)
        assert raw_e.dcn_route == "emulated", raw_e.dcn_route
        raw_s, _, l_shardmap = run(TrainConfig(optimizer=opt, dcn_pods=2),
                                   mesh)
        assert raw_s.dcn_route == "shard_map", raw_s.dcn_route
        assert raw_s.dcn_pods == 2
        np.testing.assert_allclose(l_emulated, l_shardmap, rtol=1e-4)

        raw_c, st, l_ef = run(TrainConfig(optimizer=opt, dcn_pods=0,
                                          dcn_compression="topk_ef"), mesh)
        assert raw_c.dcn_route == "shard_map"
        assert np.isfinite(l_ef).all()
        np.testing.assert_allclose(l_ef, l_shardmap, atol=0.05)
        leaves = jax.tree.leaves(st.ef)
        assert leaves and all(l.shape[0] == 2 for l in leaves)
        assert any("pod" in str(l.sharding.spec) for l in leaves)
        assert sum(float(jnp.abs(l).sum()) for l in leaves) > 0
        print("HIER_OK", l_shardmap)
    """)
    assert "HIER_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_compressed_cross_pod_allreduce():
    r = _run_py("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.dist.compression import cross_pod_allreduce
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("pod",))
        x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
        xs = jax.device_put(x, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("pod", None)))
        out = cross_pod_allreduce(xs, mesh, axis="pod", method="int8")
        expect = np.broadcast_to(np.asarray(x).sum(0, keepdims=True), (8, 4))
        err = np.abs(np.asarray(out) - expect).max() / expect.max()
        assert err < 0.05, err
        print("XPOD_OK")
    """)
    assert "XPOD_OK" in r.stdout, r.stdout + r.stderr
