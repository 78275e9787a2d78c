"""Property-style coverage for the dist substrate beyond the seed
contract: checkpoint behaviour under concurrent async saves, RULE_PRESETS
round-trips through tree_shardings, and compression determinism."""

import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.checkpoint import CheckpointManager
from repro.dist.compression import (
    compress_tree,
    cross_pod_allreduce,
    init_error_state,
    topk_ef_compress,
)
from repro.dist.sharding import (
    DEFAULT_RULES,
    RULE_PRESETS,
    ShardingRules,
    logical_to_spec,
    set_mesh,
    tree_shardings,
)
from repro.dist.straggler import Action, HeartbeatRegistry, StragglerMonitor
from repro.launch.mesh import make_mesh


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32)),
        "nested": {"b": jnp.asarray(rng.normal(size=(4,)).astype(np.float32)),
                   "step": jnp.asarray(np.int32(seed))},
    }


class TestCheckpointConcurrency:
    def test_concurrent_save_async_all_valid(self, tmp_path):
        """Interleaved save_async calls from multiple threads must leave
        only complete, valid step directories (atomic rename + keep GC)."""
        mgr = CheckpointManager(tmp_path, keep=4)
        threads = [threading.Thread(target=mgr.save_async, args=(s, _tree(s)))
                   for s in range(1, 9)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        mgr.wait()
        steps = mgr.list_steps()
        assert len(steps) == 4
        for s in steps:
            assert mgr.validate(s), s
            got = mgr.restore(s, _tree())
            np.testing.assert_array_equal(np.asarray(got["w"]),
                                          np.asarray(_tree(s)["w"]))
        # no torn .tmp directories left behind
        assert not list(tmp_path.glob("*.tmp*"))

    def test_async_then_sync_same_step_overwrites(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save_async(7, _tree(1))
        mgr.wait()
        mgr.save(7, _tree(2))
        got = mgr.restore(7, _tree())
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(_tree(2)["w"]))

    def test_restore_latest_empty_dir_is_none(self, tmp_path):
        assert CheckpointManager(tmp_path).restore_latest(_tree()) is None

    def test_bf16_leaves_roundtrip(self, tmp_path):
        """Non-numpy-native dtypes survive the byte-view encoding."""
        mgr = CheckpointManager(tmp_path)
        tree = {"p": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4)}
        mgr.save(1, tree)
        got = mgr.restore(1, tree)
        assert got["p"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(got["p"], np.float32),
                                      np.asarray(tree["p"], np.float32))


class TestRulePresets:
    def setup_method(self):
        set_mesh(None)

    @pytest.mark.parametrize("preset", sorted(RULE_PRESETS))
    def test_tree_shardings_roundtrip_1device(self, preset):
        """Every preset must produce valid shardings on a 1-device mesh
        (the degradation guarantee), and device_put through them must
        preserve values exactly."""
        rules = RULE_PRESETS[preset]
        mesh = make_mesh((1, 1), ("data", "model"))
        axes = {"emb": ("vocab", "fsdp"),
                "attn": {"wq": ("fsdp", "heads", None)},
                "scale": (None,),
                "step": ()}
        tree = {"emb": jnp.ones((32, 16)),
                "attn": {"wq": jnp.ones((16, 4, 8))},
                "scale": jnp.ones((16,)),
                "step": jnp.zeros(())}
        sh = tree_shardings(axes, tree, mesh, rules)
        placed = jax.tree.map(jax.device_put, tree, sh)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(placed)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fsdp_only_preset_never_uses_model_axis(self):
        # spec resolution only reads mesh.shape, so a stub stands in for
        # the 8-device mesh this CPU process cannot build
        mesh = types.SimpleNamespace(shape={"data": 2, "model": 4})
        rules = RULE_PRESETS["fsdp_only"]
        for name in ("heads", "ff", "experts", "vocab", "seq_shard"):
            spec = logical_to_spec((name,), (8,), mesh, rules)
            assert "model" not in jax.tree.leaves(tuple(spec)), (name, spec)

    def test_partial_multi_axis_divisibility(self):
        """batch -> ('pod','data'): a dim divisible by pod but not by
        pod*data shards over pod only."""
        set_mesh(None)
        mesh = types.SimpleNamespace(shape={"pod": 2, "data": 3, "model": 1})
        spec = logical_to_spec(("batch",), (4,), mesh, DEFAULT_RULES)
        assert spec == jax.sharding.PartitionSpec("pod")

    def test_unknown_logical_axis_raises(self):
        with pytest.raises(AttributeError):
            DEFAULT_RULES.lookup("not_an_axis")

    def test_replace_is_pure(self):
        r = DEFAULT_RULES.replace(kv_seq="model")
        assert DEFAULT_RULES.kv_seq is None
        assert r.kv_seq == "model"
        assert isinstance(r, ShardingRules)


class TestCompressionDeterminism:
    def test_int8_deterministic_under_fixed_key(self):
        g = {"w": jnp.asarray(np.random.default_rng(3).normal(
            size=(64, 32)).astype(np.float32))}
        key = jax.random.PRNGKey(7)
        a = compress_tree(g, method="int8", key=key)
        b = compress_tree(g, method="int8", key=key)
        np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(b["w"]))
        c = compress_tree(g, method="int8", key=jax.random.PRNGKey(8))
        assert not np.array_equal(np.asarray(a["w"]), np.asarray(c["w"]))

    def test_int8_under_jit_matches_eager(self):
        g = {"w": jnp.linspace(-1.0, 1.0, 128).reshape(8, 16)}
        eager = compress_tree(g, method="int8")
        jitted = jax.jit(lambda t: compress_tree(t, method="int8"))(g)
        np.testing.assert_allclose(np.asarray(eager["w"]),
                                   np.asarray(jitted["w"]), rtol=1e-6)

    def test_topk_zero_frac_keeps_at_least_one(self):
        g = {"w": jnp.asarray([0.0, 3.0, -1.0, 0.5])}
        out = compress_tree(g, method="topk", topk_frac=0.0)
        nz = np.nonzero(np.asarray(out["w"]))[0]
        assert list(nz) == [1]  # the single largest coordinate

    def test_ef_state_stays_finite_over_many_steps(self):
        rng = np.random.default_rng(0)
        g = {"w": jnp.asarray(rng.normal(size=(64,)).astype(np.float32))}
        err = init_error_state(g)
        for _ in range(50):
            _, err = topk_ef_compress(g, err, topk_frac=0.1)
        assert np.isfinite(np.asarray(err["w"])).all()

    def test_cross_pod_allreduce_1device(self):
        mesh = make_mesh((1,), ("pod",))
        x = jnp.arange(8, dtype=jnp.float32).reshape(1, 8)
        out = cross_pod_allreduce(x, mesh, axis="pod", method="none")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


class TestStragglerEdges:
    def test_evict_resets_streak(self):
        m = StragglerMonitor(warmup_steps=2, consecutive_limit=2)
        for _ in range(5):
            m.observe(1.0)
        assert m.observe(9.0) == Action.WARN
        assert m.observe(9.0) == Action.EVICT
        # streak reset: the next slow step starts a new WARN cycle
        assert m.observe(9.0) == Action.WARN

    def test_heartbeat_recovers_after_beat(self):
        reg = HeartbeatRegistry(num_hosts=2, timeout_steps=2)
        reg.beat(0)
        assert reg.tick() == []          # nobody has missed 2 ticks yet
        reg.beat(0)
        assert reg.tick() == [1]         # 1 has been silent for 2 ticks
        reg.beat(1)
        assert reg.tick() == [0]         # 0 went quiet, 1 recovered
