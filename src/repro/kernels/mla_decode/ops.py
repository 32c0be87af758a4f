"""Jit'd public wrapper for the MLA decode kernel.

The Pallas kernel runs natively (the serving path lowers it for a TPU
only); ``interpret=True`` runs its body on the CPU, for the correctness
tests.  The rope query is padded with zeros to the rope pool's width and
the lengths are clamped to the tables' reach.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .mla_decode import mla_decode_fwd

PAGES = 8        # pages per compute block: 128 rows at 16-row pages


@partial(jax.jit, static_argnames=("scale", "pages", "interpret"))
def mla_decode(q_lat, q_rope, ckv_pages, krope_pages, tables, lens, layer, *,
               scale, pages=PAGES, interpret=False):
    """q_lat: [B, H, R] absorbed queries; q_rope: [B, H, rope] roped
    queries; ckv_pages: [L, N, bs, R] and krope_pages: [L, N, bs, Kw >=
    rope] a layer scan's stacked pools; tables: [B, W] page ids; lens: [B]
    rows each lane reads (0: none); layer: int32 scalar; scale: the
    softmax scale.  Returns o_lat [B, H, R], zeros for a lane of length
    0."""
    pad = krope_pages.shape[-1] - q_rope.shape[-1]
    q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, pad)))
    lens = jnp.clip(lens, 0, tables.shape[1] * ckv_pages.shape[2])
    return mla_decode_fwd(q_lat, q_rope.astype(q_lat.dtype), ckv_pages,
                          krope_pages, tables, lens, layer, scale=scale,
                          pages=pages, interpret=interpret)
