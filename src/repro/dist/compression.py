"""Gradient compression for cross-pod sync: stochastic-rounding int8,
magnitude top-k, error-feedback top-k, compressed all-reduces, and the
DCN wire-format accounting behind the train step's ``dcn_bytes`` metric.

All compressors are simulate-on-device: they return the *decompressed*
values (same shapes/dtypes as the input) so they compose with any
optimizer; the wire format is implied by the math (int8 codes + one fp32
scale per leaf, or exactly-k (index, value) pairs) and is what
``tree_wire_bytes`` accounts.

Stochastic rounding (``floor(x/s + u)``, u ~ U[0,1)) keeps int8
quantization unbiased — E[q·s] = x — so compressed SGD converges like a
noisier uncompressed SGD instead of accumulating rounding bias. The
rounding key should change every step (``per_step_key``; the train step
folds ``TrainState.step`` in) — a fixed key draws the *same* noise each
step, which correlates the rounding error across the whole run. Top-k
alone silently drops small coordinates forever; ``topk_ef_compress``
carries the error state so every coordinate is eventually transmitted
(the EF-SGD invariant: sent + new_err == grads + old_err, exactly).

Mesh axes: the collectives here sum over exactly one named axis — by
convention ``'pod'``, the slow DCN hop of the multi-pod mesh
(``repro.launch.mesh``). ``cross_pod_allreduce`` is the single-array
form; ``dcn_allreduce_tree`` is the train-step form, taking a gradient
pytree stacked along a leading per-pod dim plus the per-pod
error-feedback state, compressing each pod's payload *before* the psum
crosses the axis. The in-graph compressors (``compress_tree``,
``topk_ef_compress``, ``dcn_send``) are axis-free and run under any
sharding. Degradation/fallback: ``method='none'`` short-circuits to the
identity (resp. a plain psum on the wire path, bit-identical to an
uncompressed all-reduce); a size-1 axis makes the psum a no-op so the
code needs no special case; the shard_map closure is lru-cached per
(mesh, axis, method, rank) so per-step calls never retrace.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

DCN_METHODS = ("none", "int8", "topk", "topk_ef")


def per_step_key(seed: int, step) -> jax.Array:
    """Per-step rounding key: PRNGKey(seed) with the step counter folded
    in, so stochastic-rounding noise decorrelates across steps."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), step)


def _int8_stochastic(x: jax.Array, key: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.abs(xf).max(), 1e-30) / 127.0
    u = jax.random.uniform(key, xf.shape)
    q = jnp.clip(jnp.floor(xf / scale + u), -127, 127)
    return (q * scale).astype(x.dtype)


def topk_count(n: int, frac: float) -> int:
    """Coordinates kept by top-k on an n-element leaf: max(round(frac*n), 1)."""
    return max(int(round(frac * n)), 1)


def _topk_mask(x: jax.Array, frac: float) -> jax.Array:
    """0/1 mask selecting *exactly* ``topk_count`` coordinates by |value|,
    ties broken toward the lower flat index (``lax.top_k`` order) — exact
    cardinality is what the (index, value)-pair wire accounting assumes."""
    flat = jnp.abs(x.astype(jnp.float32)).reshape(-1)
    k = topk_count(flat.size, frac)
    idx = jax.lax.top_k(flat, k)[1]
    mask = jnp.zeros(flat.shape, x.dtype).at[idx].set(1)
    return mask.reshape(x.shape)


def _topk(x: jax.Array, frac: float) -> jax.Array:
    return x * _topk_mask(x, frac)


def compress_tree(grads, method: str = "int8", topk_frac: float = 0.01,
                  key: jax.Array | None = None):
    """Compress+decompress every leaf. ``method``: none | int8 | topk.

    ``key`` seeds the int8 stochastic rounding. The default is the fixed
    legacy key (deterministic under jit, still unbiased per element draw,
    but *identical noise every call*) — training callers should pass
    ``per_step_key(seed, step)`` so rounding noise decorrelates across
    steps instead of accumulating a correlated bias."""
    if method == "none":
        return grads
    if method == "topk":
        return jax.tree.map(lambda g: _topk(g, topk_frac), grads)
    if method != "int8":
        raise ValueError(f"unknown compression method: {method}")
    if key is None:
        key = jax.random.PRNGKey(0)
    leaves, treedef = jax.tree.flatten(grads)
    out = [_int8_stochastic(g, jax.random.fold_in(key, i))
           for i, g in enumerate(leaves)]
    return jax.tree.unflatten(treedef, out)


def init_error_state(grads):
    """Zero error-feedback residuals mirroring the grad tree (fp32)."""
    return jax.tree.map(
        lambda g: jnp.zeros(jnp.shape(g), jnp.float32), grads)


def topk_ef_compress(grads, error_state, topk_frac: float = 0.01):
    """Error-feedback top-k: returns (sent, new_error_state).

    sent + new_error == grads + old_error holds exactly (the masks are
    complementary selections of the same accumulator), which is the
    invariant that makes EF-SGD converge at the uncompressed rate."""
    def one(g, e):
        acc = g.astype(jnp.float32) + e
        mask = _topk_mask(acc, topk_frac)
        return acc * mask, acc * (1.0 - mask)

    pairs = jax.tree.map(one, grads, error_state)
    sent = jax.tree.map(lambda p: p[0], pairs,
                        is_leaf=lambda x: isinstance(x, tuple))
    err = jax.tree.map(lambda p: p[1], pairs,
                       is_leaf=lambda x: isinstance(x, tuple))
    return sent, err


def dcn_send(grads, error, method: str = "int8", topk_frac: float = 0.01,
             key: jax.Array | None = None):
    """One pod's DCN payload: ``(sent, new_error)``.

    The unit shared by the emulated and shard_map hierarchical reduces
    (and property-tested directly): ``sent`` is what this pod puts on the
    wire, ``new_error`` the residual it keeps. ``error`` is ``{}`` for
    the stateless methods (none/int8/topk) and a grads-shaped fp32 tree
    for ``topk_ef`` (the EF-SGD invariant ``sent + new_error == grads +
    error`` holds bit-for-bit). ``method='none'`` is the identity."""
    if method == "none":
        return grads, error
    if method == "topk_ef":
        return topk_ef_compress(grads, error, topk_frac)
    return compress_tree(grads, method=method, topk_frac=topk_frac,
                         key=key), error


def leaf_wire_bytes(n: int, method: str, topk_frac: float = 0.01) -> int:
    """Bytes one n-element fp32 leaf costs on the DCN per pod per step.

    none: 4n (raw fp32). int8: n codes + one fp32 scale. topk/topk_ef:
    exactly-k (int32 index, fp32 value) pairs, k = ``topk_count``."""
    if method == "none":
        return 4 * n
    if method == "int8":
        return n + 4
    if method in ("topk", "topk_ef"):
        return 8 * topk_count(n, topk_frac)
    raise ValueError(f"unknown compression method: {method}")


def tree_wire_bytes(tree, method: str, topk_frac: float = 0.01) -> int:
    """Total per-pod DCN bytes for one send of a gradient pytree."""
    return sum(leaf_wire_bytes(math.prod(jnp.shape(l)) or 1, method,
                               topk_frac)
               for l in jax.tree.leaves(tree))


def dcn_allreduce_tree(grads_stacked, error, mesh: Mesh, axis: str = "pod",
                       method: str = "int8", topk_frac: float = 0.01,
                       key: jax.Array | None = None):
    """Compressed all-reduce of a *stacked* gradient pytree over one mesh
    axis — the train step's DCN hop.

    ``grads_stacked`` leaves are ``(P, *shape)`` with the leading per-pod
    dim sharded over ``axis`` (P = axis size); ``error`` is ``{}`` or a
    matching ``(P, *shape)`` per-pod EF tree. Each pod compresses its own
    slice (rounding key = ``fold_in(key, axis_index)``, matching the
    emulated route's ``fold_in(key, pod)``) and only then psums across
    ``axis``, so the slow hop carries the compressed payload while the
    in-pod reduction that produced the slice stayed uncompressed on ICI.

    Memory note: compression is whole-leaf (one int8 scale / one top-k
    selection per leaf, the same math as the emulated route), so entering
    the collective gathers each pod's full gradient tree onto its devices
    — the same footprint as an unsharded all-reduce buffer. Keeping
    gradient FSDP sharding *through* the collective would need
    shard-local compression (per-shard top-k/scales), a different wire
    format tracked as a ROADMAP follow-up.
    Returns ``(summed tree without the leading dim, new per-pod error)``;
    scaling by 1/P is the caller's job. ``method='none'`` degrades to a
    plain psum — bit-identical to an uncompressed all-reduce.

    Per-step callers MUST pass a fresh ``key`` (the train step threads
    ``per_step_key(seed, step)``): the ``None`` default is the fixed
    legacy key, which draws *identical* int8 rounding noise every call —
    the correlated-bias failure mode this module exists to avoid."""
    if method not in DCN_METHODS:
        raise ValueError(f"unknown compression method: {method}")
    if key is None:
        key = jax.random.PRNGKey(0)

    def local(gP, eP, k):
        g = jax.tree.map(lambda x: jnp.squeeze(x, 0), gP)
        e = jax.tree.map(lambda x: jnp.squeeze(x, 0), eP)
        pod = jax.lax.axis_index(axis)
        sent, new_e = dcn_send(g, e, method, topk_frac,
                               jax.random.fold_in(k, pod))
        red = jax.tree.map(lambda x: jax.lax.psum(x, axis), sent)
        return red, jax.tree.map(lambda x: x[None], new_e)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(axis), P(axis), P()),
                       out_specs=(P(), P(axis)), check_vma=False)
    return fn(grads_stacked, error, key)


@functools.lru_cache(maxsize=None)
def _allreduce_fn(mesh: Mesh, axis: str, method: str, topk_frac: float,
                  ndim: int):
    """Build + jit once per (mesh, axis, method, rank): callers invoke
    this every step, so the closure must be cached or each call would
    retrace and recompile."""
    spec = P(axis, *([None] * (ndim - 1)))

    def local(xl, key):
        if method == "int8":
            idx = jax.lax.axis_index(axis)
            xl = _int8_stochastic(xl, jax.random.fold_in(key, idx))
        elif method == "topk":
            xl = _topk(xl, topk_frac)
        return jax.lax.psum(xl, axis)

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(spec, P(None)),
                                 out_specs=spec, check_vma=False))


def cross_pod_allreduce(x: jax.Array, mesh: Mesh, axis: str = "pod",
                        method: str = "int8", topk_frac: float = 0.01,
                        key: jax.Array | None = None) -> jax.Array:
    """All-reduce (sum) over one mesh axis with per-shard compression
    applied before the wire — the cheap DCN cross-pod gradient sync.

    ``x`` is sharded over ``axis`` on its leading dim; the result has the
    same sharding with every shard holding the full sum (all-reduce
    semantics), compressed to ~8 bits/element for ``method='int8'``.
    Per-step callers should pass ``key=per_step_key(seed, step)`` for
    fresh rounding noise; with no key, the fixed legacy key is used.
    """
    if method not in ("none", "int8", "topk"):
        raise ValueError(f"unknown compression method: {method}")
    if key is None:
        key = jax.random.PRNGKey(0)
    return _allreduce_fn(mesh, axis, method, topk_frac, x.ndim)(x, key)
