"""Float32 oracle for the MLA decode kernel.

Each lane's first ``lens[b]`` rows are gathered from layer ``layer`` of
the stacked pools through its table, scored against the absorbed and
rope queries, and averaged under a softmax, all in float32; a lane of
length 0 gets zeros.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mla_decode(q_lat: jax.Array, q_rope: jax.Array, ckv_pages: jax.Array,
               krope_pages: jax.Array, tables: jax.Array, lens: jax.Array,
               layer, *, scale: float) -> jax.Array:
    """Shapes as ``ops.mla_decode``.  Returns o_lat [B, H, R] in float32."""
    f32 = jnp.float32
    B, W = tables.shape
    bs = ckv_pages.shape[2]
    rope = q_rope.shape[-1]
    ckv = ckv_pages[layer, tables].reshape(B, W * bs, -1).astype(f32)
    kr = krope_pages[layer, tables].reshape(B, W * bs, -1)[..., :rope]
    s = (jnp.einsum("bhr,bkr->bhk", q_lat.astype(f32), ckv) +
         jnp.einsum("bhd,bkd->bhk", q_rope.astype(f32), kr.astype(f32)))
    live = jnp.arange(W * bs)[None, None, :] < lens[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s * scale, -jnp.inf), axis=-1)
    o = jnp.einsum("bhk,bkr->bhr", jnp.where(live, p, 0.0), ckv)
    return jnp.where(lens[:, None, None] > 0, o, 0.0)
