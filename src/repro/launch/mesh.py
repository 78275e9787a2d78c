"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax use.

Mesh axes:
  single-pod:  (16, 16)      -> ('data', 'model')   = 256 chips (one v5e pod)
  multi-pod:   (2, 16, 16)   -> ('pod', 'data', 'model') = 512 chips

'pod'  — pure data parallelism across pods (grad all-reduce over DCN),
'data' — data parallel + FSDP weight sharding within a pod,
'model'— tensor/expert parallelism within a pod.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """The one mesh constructor of the repo: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
    ``with_sharding_constraint`` (``repro.dist.sharding.constrain``) and
    sharded gathers raise; this code relies on the compiler propagating
    shardings, which needs ``Auto``.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None):
    """Small mesh over however many (host) devices exist — used by tests."""
    n = n_devices or len(jax.devices())
    model = 1
    for cand in (4, 2, 1):
        if n % cand == 0:
            model = cand
            break
    return make_mesh((n // model, model), ("data", "model"))
