"""Property tests for repro.dist.compression — the invariants that make
compressed cross-pod gradient sync safe to run for millions of steps:

* EF-SGD conservation: ``sent + new_err == grads + old_err`` holds
  *bit-for-bit* in fp32 (masks are complementary selections of one
  accumulator), for any grads/residual and any top-k fraction.
* int8 stochastic rounding is unbiased within statistical tolerance when
  averaged over many rounding keys (and bounded by one quantization step
  elementwise for every key).
* top-k keeps exactly ``max(round(frac * n), 1)`` coordinates — ties
  included (exact cardinality is what the (index, value) wire-format
  accounting in ``tree_wire_bytes`` assumes).
* ``method='none'`` is the identity, and the per-step key threading
  actually changes the rounding noise between steps.

Strategies stick to the integers/floats/sampled_from subset that both
real hypothesis (CI) and the deterministic conftest micro-shim provide.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.dist.compression import (
    compress_tree,
    dcn_allreduce_tree,
    dcn_send,
    init_error_state,
    leaf_wire_bytes,
    per_step_key,
    topk_count,
    topk_ef_compress,
    tree_wire_bytes,
)
from repro.launch.mesh import make_mesh


def _grad_tree(seed: int, n: int):
    """A small two-level grads pytree with an n-element and an n//3+1
    element leaf (multi-leaf trees exercise the per-leaf key fold)."""
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(n,)).astype(np.float32)),
        "inner": {"b": jnp.asarray(
            rng.normal(size=(n // 3 + 1,)).astype(np.float32))},
    }


# ---------------------------------------------------------------------------
# EF-SGD conservation
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 257),
       st.sampled_from([0.01, 0.1, 0.25, 0.5, 1.0]))
def test_ef_invariant_exact(seed, n, frac):
    """sent + new_err == grads + old_err, bit-for-bit in fp32, with a
    *nonzero* incoming residual (the steady-state case, not just step 0)."""
    grads = _grad_tree(seed, n)
    err = _grad_tree(seed + 1, n)  # arbitrary prior residual
    sent, new_err = topk_ef_compress(grads, err, topk_frac=frac)
    for g, e, s, ne in zip(jax.tree.leaves(grads), jax.tree.leaves(err),
                           jax.tree.leaves(sent), jax.tree.leaves(new_err)):
        lhs = np.asarray(s) + np.asarray(ne)     # fp32 adds, like the rhs
        rhs = np.asarray(g) + np.asarray(e)
        np.testing.assert_array_equal(lhs, rhs)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 200))
def test_ef_sent_and_residual_disjoint(seed, n):
    """A coordinate is either sent or kept — never both, never scaled."""
    grads = _grad_tree(seed, n)
    err = init_error_state(grads)
    sent, new_err = topk_ef_compress(grads, err, topk_frac=0.25)
    for s, ne in zip(jax.tree.leaves(sent), jax.tree.leaves(new_err)):
        assert not np.any((np.asarray(s) != 0) & (np.asarray(ne) != 0))


# ---------------------------------------------------------------------------
# int8 stochastic rounding
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.integers(0, 1_000))
def test_int8_unbiased_over_keys(seed):
    """E[decompress(compress(x))] == x: the mean rounding error over many
    keys shrinks as 1/sqrt(K), far inside a 5%-of-scale budget."""
    rng = np.random.default_rng(seed)
    x = {"w": jnp.asarray(rng.normal(size=(256,)).astype(np.float32))}
    scale = float(jnp.abs(x["w"]).max()) / 127.0
    fn = jax.jit(lambda key: compress_tree(x, method="int8", key=key)["w"])
    keys = 64
    acc = np.zeros(256, np.float64)
    for k in range(keys):
        out = np.asarray(fn(jax.random.PRNGKey(seed * keys + k)))
        err = out - np.asarray(x["w"])
        assert np.abs(err).max() <= scale + 1e-6  # bounded for every key
        acc += err
    # mean over 64 keys x 256 elements: sigma ~ scale/sqrt(12*16384)
    assert abs(acc.mean() / keys) < 0.05 * scale


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(64, 300))
def test_int8_key_threading(seed, n):
    """Same key -> identical codes; per-step keys -> fresh noise. The
    pre-fix behavior (no key argument) stays the fixed legacy key."""
    grads = _grad_tree(seed, n)
    k5 = per_step_key(0, 5)
    a = compress_tree(grads, method="int8", key=k5)
    b = compress_tree(grads, method="int8", key=k5)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    legacy1 = compress_tree(grads, method="int8")
    legacy2 = compress_tree(grads, method="int8",
                            key=jax.random.PRNGKey(0))
    for la, lb in zip(jax.tree.leaves(legacy1), jax.tree.leaves(legacy2)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    c = compress_tree(grads, method="int8", key=per_step_key(0, 6))
    same = all(np.array_equal(np.asarray(la), np.asarray(lc))
               for la, lc in zip(jax.tree.leaves(a), jax.tree.leaves(c)))
    assert not same  # a different step must draw different noise


# ---------------------------------------------------------------------------
# top-k cardinality
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 400),
       st.sampled_from([0.001, 0.01, 0.1, 0.5, 1.0]))
def test_topk_exact_count(seed, n, frac):
    """Exactly max(round(frac*n), 1) coordinates survive — even with
    heavy magnitude ties (integer-valued inputs)."""
    rng = np.random.default_rng(seed)
    # values in {-3..-1, 1..3}: no zeros, many |.| ties
    vals = rng.integers(1, 4, size=n) * rng.choice([-1.0, 1.0], size=n)
    g = {"w": jnp.asarray(vals.astype(np.float32))}
    out = compress_tree(g, method="topk", topk_frac=frac)
    assert int(np.count_nonzero(np.asarray(out["w"]))) == topk_count(n, frac)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 300),
       st.sampled_from([0.01, 0.1, 0.25]))
def test_topk_ef_exact_count(seed, n, frac):
    """The EF send keeps the same exact cardinality on its accumulator."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(1, 4, size=n) * rng.choice([-1.0, 1.0], size=n)
    g = {"w": jnp.asarray(vals.astype(np.float32))}
    sent, _ = topk_ef_compress(g, init_error_state(g), topk_frac=frac)
    assert int(np.count_nonzero(np.asarray(sent["w"]))) == topk_count(n, frac)


def test_topk_keeps_largest_magnitudes():
    g = {"w": jnp.asarray(np.asarray(
        [0.1, -5.0, 0.2, 4.0, -0.3, 3.0, 0.05, -2.0], np.float32))}
    out = np.asarray(compress_tree(g, method="topk", topk_frac=0.5)["w"])
    np.testing.assert_array_equal(
        out, np.asarray([0, -5.0, 0, 4.0, 0, 3.0, 0, -2.0], np.float32))


# ---------------------------------------------------------------------------
# identity + dcn_send plumbing
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 300))
def test_none_is_identity(seed, n):
    grads = _grad_tree(seed, n)
    out = compress_tree(grads, method="none")
    assert out is grads  # short-circuit, not a copy
    sent, err = dcn_send(grads, {}, method="none")
    assert sent is grads and err == {}


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 200),
       st.sampled_from(["int8", "topk"]))
def test_dcn_send_stateless_methods_keep_error(seed, n, method):
    """Stateless methods pass the (empty) error tree through untouched."""
    grads = _grad_tree(seed, n)
    sent, err = dcn_send(grads, {}, method=method, key=per_step_key(0, 1))
    assert err == {}
    assert jax.tree.structure(sent) == jax.tree.structure(grads)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 200),
       st.sampled_from([0.01, 0.25]))
def test_dcn_send_topk_ef_matches_topk_ef_compress(seed, n, frac):
    grads = _grad_tree(seed, n)
    err = _grad_tree(seed + 7, n)
    a = dcn_send(grads, err, method="topk_ef", topk_frac=frac)
    b = topk_ef_compress(grads, err, topk_frac=frac)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# wire-format accounting
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(1, 100_000),
       st.sampled_from([0.001, 0.01, 0.1, 1.0]))
def test_leaf_wire_bytes_formulas(n, frac):
    assert leaf_wire_bytes(n, "none") == 4 * n
    assert leaf_wire_bytes(n, "int8") == n + 4
    assert (leaf_wire_bytes(n, "topk", frac)
            == leaf_wire_bytes(n, "topk_ef", frac)
            == 8 * topk_count(n, frac))


def test_tree_wire_bytes_sums_leaves():
    tree = {"a": jnp.zeros((8, 4)), "b": {"c": jnp.zeros((3,))}}
    assert tree_wire_bytes(tree, "none") == 4 * 35
    assert tree_wire_bytes(tree, "int8") == (32 + 4) + (3 + 4)
    # 1% of 32 rounds to 0 -> floor of one coordinate per leaf
    assert tree_wire_bytes(tree, "topk", 0.01) == 8 * (1 + 1)


def test_topk_wire_bytes_beat_raw_by_4x():
    """The acceptance-bar ratio: top-k at the default 1% fraction moves
    >=4x fewer bytes than raw fp32 on realistically-sized leaves."""
    tree = {"w": jnp.zeros((4096, 128))}
    raw = tree_wire_bytes(tree, "none")
    for method in ("topk", "topk_ef"):
        assert raw / tree_wire_bytes(tree, method, 0.01) >= 4.0


# ---------------------------------------------------------------------------
# dcn_allreduce_tree degradation (single-device 'pod' axis of size 1 —
# the real multi-pod collective runs in tests/test_multidevice.py)
# ---------------------------------------------------------------------------

def test_dcn_allreduce_tree_single_pod_none_is_identity():
    mesh = make_mesh((1,), ("pod",))
    grads = _grad_tree(0, 64)
    stacked = jax.tree.map(lambda x: x[None], grads)
    red, new_ef = dcn_allreduce_tree(stacked, {}, mesh, method="none")
    assert new_ef == {}
    for a, b in zip(jax.tree.leaves(red), jax.tree.leaves(grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dcn_allreduce_tree_single_pod_topk_ef_invariant():
    """Through the shard_map wrapper, the EF invariant still holds:
    reduced + residual == grads + old residual (one pod, so the psum is
    the send itself)."""
    mesh = make_mesh((1,), ("pod",))
    grads = _grad_tree(1, 64)
    err = _grad_tree(2, 64)
    stacked = jax.tree.map(lambda x: x[None], grads)
    err_s = jax.tree.map(lambda x: x[None], err)
    red, new_ef = dcn_allreduce_tree(stacked, err_s, mesh,
                                     method="topk_ef", topk_frac=0.25)
    for r, ne, g, e in zip(jax.tree.leaves(red), jax.tree.leaves(new_ef),
                           jax.tree.leaves(grads), jax.tree.leaves(err)):
        lhs = np.asarray(r) + np.asarray(ne)[0]
        np.testing.assert_array_equal(lhs, np.asarray(g) + np.asarray(e))


def test_dcn_allreduce_tree_rejects_unknown_method():
    mesh = make_mesh((1,), ("pod",))
    with pytest.raises(ValueError):
        dcn_allreduce_tree({"w": jnp.zeros((1, 4))}, {}, mesh,
                           method="zstd")
