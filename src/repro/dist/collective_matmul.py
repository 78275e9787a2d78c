"""Collective matmuls: decomposed collectives interleaved with compute.

The XLA-default pattern for a TP matmul is matmul-then-all-reduce (or
all-gather-then-matmul): the collective and the MXU serialize. These
kernels decompose the collective into ``n-1`` ring steps (ppermute) and
issue a partial matmul per step, so the interconnect and the MXU run
concurrently — the "collective matmul" trick (Wang et al., ASPLOS'23)
that the roofline cells show is required once ICI time ~= compute time.

Mesh axes: both kernels ring over a single named axis — ``'model'`` by
default, the fast-ICI tensor-parallel axis of the production mesh
(``repro.launch.mesh``). ``ring_matmul_reduce`` shards the contraction
dim of ``x`` and the rows of ``w`` over it; ``ag_matmul_pipelined``
shards the rows of ``x`` and the columns of ``w``.

Degradation/fallback: both functions compute exactly ``x @ w`` for any
mesh-axis size. A size-1 axis degrades to a plain local matmul (the
ring has zero ppermute steps), and dims not divisible by the axis size
fall back to the unsharded ``x @ w`` rather than erroring — the same
replicate-on-indivisibility contract as ``repro.dist.sharding``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


@functools.lru_cache(maxsize=None)
def _ring_fn(mesh: Mesh, axis: str):
    n = mesh.shape[axis]

    def local(xl, wl):
        partial = xl @ wl
        acc = partial
        chunk = partial
        perm = [(i, (i + 1) % n) for i in range(n)]
        for _ in range(n - 1):
            chunk = jax.lax.ppermute(chunk, axis, perm)
            acc = acc + chunk
        return acc

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(None, axis), P(axis, None)),
                                 out_specs=P(None, None), check_vma=False))


def ring_matmul_reduce(x: jax.Array, w: jax.Array, mesh: Mesh,
                       axis: str = "model") -> jax.Array:
    """x @ w with the contraction dim sharded over ``axis``.

    Each device matmuls its k-shard into a full-size partial, then the
    partials circulate the ring accumulating — an unrolled all-reduce
    whose steps overlap the next shard's compute. Output is replicated
    over ``axis``.
    """
    if x.shape[-1] % mesh.shape[axis]:
        # indivisible contraction dim: no sharding to exploit
        return x @ w
    return _ring_fn(mesh, axis)(x, w)


@functools.lru_cache(maxsize=None)
def _ag_fn(mesh: Mesh, axis: str):
    n = mesh.shape[axis]

    def local(xl, wl):
        m_l = xl.shape[0]
        idx = jax.lax.axis_index(axis)
        out = jnp.zeros((m_l * n, wl.shape[-1]), jnp.result_type(xl, wl))
        chunk = xl
        perm = [(i, (i + 1) % n) for i in range(n)]
        for t in range(n):
            src = jnp.mod(idx - t, n)
            out = jax.lax.dynamic_update_slice(out, chunk @ wl,
                                               (src * m_l, 0))
            if t < n - 1:
                chunk = jax.lax.ppermute(chunk, axis, perm)
        return out

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(axis, None), P(None, axis)),
                                 out_specs=P(None, axis), check_vma=False))


def ag_matmul_pipelined(x: jax.Array, w: jax.Array, mesh: Mesh,
                        axis: str = "model") -> jax.Array:
    """x @ w with x row-sharded and w column-sharded over ``axis``.

    Each device needs all rows of x for its column shard of the output;
    instead of a blocking all-gather, row-chunks of x circulate the ring
    and each arriving chunk is matmul'd immediately into its slot of the
    local output block (pipelined all-gather + matmul).
    """
    n = mesh.shape[axis]
    if x.shape[0] % n or w.shape[-1] % n:
        return x @ w
    return _ag_fn(mesh, axis)(x, w)
