"""Public jit'd wrapper for fused int8-KV decode attention."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.block_utils import default_interpret
from repro.kernels.decode_attention.decode_attention import decode_attention_pallas_call


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def decode_attention_pallas(
    q: jax.Array,        # (B, KV, G, hd)
    k8: jax.Array,       # (B, S, KV, hd) int8
    v8: jax.Array,
    k_scale: jax.Array,  # (B, S, KV) f32
    v_scale: jax.Array,
    valid_len: jax.Array,  # () int32
    *,
    chunk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    s = k8.shape[1]
    chunk = min(chunk, s)
    if s % chunk:
        pad = chunk - s % chunk
        k8 = jnp.pad(k8, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v8 = jnp.pad(v8, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
    vl = jnp.reshape(valid_len.astype(jnp.int32), (1, 1))
    return decode_attention_pallas_call(
        q.astype(jnp.float32), k8, v8,
        k_scale.astype(jnp.float32), v_scale.astype(jnp.float32), vl,
        chunk=chunk, interpret=interpret)
