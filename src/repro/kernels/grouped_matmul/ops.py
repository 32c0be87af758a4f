"""Jit'd public wrapper for the grouped expert-FFN kernel.

The Pallas kernel runs natively (the serving path lowers it for a TPU
only); ``interpret=True`` runs its body on the CPU, for the correctness
tests.  The rows are padded to whole ``tm``-row tiles and the padding cut
off again.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .grouped_matmul import grouped_ffn_fwd

TM = 128


@partial(jax.jit, static_argnames=("act", "tm", "interpret"))
def grouped_ffn(x, w_gate, w_up, w_down, group_sizes, layer, *,
                act=jax.nn.silu, tm=TM, interpret=False):
    """x: [m, d] rows sorted by group; w_gate, w_up: [L, G, d, ff]; w_down:
    [L, G, ff, d]; group_sizes: [G] int32, summing to at most m; layer:
    int32 scalar; act: the gate's activation.  Returns [m, d]: each
    group's rows through its own expert of layer ``layer``; rows past the
    last group are unspecified."""
    m = x.shape[0]
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = grouped_ffn_fwd(x, w_gate, w_up, w_down,
                          group_sizes.astype(jnp.int32), layer, tm=tm,
                          act=act, interpret=interpret)
    return out[:m] if pad else out
