"""Jit'd public wrapper for the RG-LRU scan kernel.

On TPU the Pallas kernel runs natively; elsewhere it runs in interpret mode
(the kernel body executes on CPU — used by the correctness sweeps against
``ref.reference``).  a, bx: [B, S, W] gates and gated inputs; returns
(h [B, S, W], h_final [B, W]) with h_t = a_t * h_{t-1} + bx_t.

Layout: the recurrence loads and stores aligned ``[8, bw]`` row tiles and
walks their rows with static slices, and the state is a ``(1, bw)`` tile;
indexing the loaded chunk by the loop counter (``a[t]``) lowered to a
``dynamic_slice`` the TPU compiler does not implement.
"""

from __future__ import annotations

from functools import partial

import jax

from .rglru_scan import rglru_scan_fwd


@partial(jax.jit, static_argnames=("block_w", "chunk", "interpret"))
def rglru_scan(a, bx, *, block_w: int = 128, chunk: int = 256,
               interpret=None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return rglru_scan_fwd(a, bx, block_w=block_w, chunk=chunk,
                          interpret=interpret)
