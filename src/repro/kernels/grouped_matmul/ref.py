"""Pure-jnp oracle for the grouped expert FFN.

Row ``r`` of group ``g`` (groups are consecutive runs of rows, sized by
``group_sizes``) is ``(act(x[r] @ w_gate[l, g]) * (x[r] @ w_up[l, g])) @
w_down[l, g]`` for the stacks' layer ``l``, the matmuls accumulated in
float32, the hidden activations
rounded to ``x``'s type as the kernel rounds them; rows past the last
group are zero.  One dense expert per group, kept where the row belongs
to it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array, group_sizes: jax.Array, layer,
                act=jax.nn.silu) -> jax.Array:
    """x: [m, d]; w_gate, w_up: [L, G, d, ff]; w_down: [L, G, ff, d];
    group_sizes: [G]; layer: the stacks' layer used. Returns [m, d]."""
    w_gate, w_up, w_down = (w[layer] for w in (w_gate, w_up, w_down))
    m = x.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    group = jnp.searchsorted(ends, jnp.arange(m, dtype=jnp.int32),
                             side="right")                  # G: no group
    dot = lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32)
    out = jnp.zeros(x.shape, jnp.float32)
    for g in range(w_gate.shape[0]):
        hidden = (act(dot(x, w_gate[g])) * dot(x, w_up[g]))
        part = dot(hidden.astype(x.dtype), w_down[g])
        out = jnp.where((group == g)[:, None], part, out)
    return out.astype(x.dtype)
