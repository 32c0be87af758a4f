"""Jit'd public wrapper for the paged-attention decode kernel.

On TPU the Pallas kernel runs natively; elsewhere it runs in interpret mode
(the kernel body executes on CPU — used by the correctness sweeps).  A head
grouping that does not divide evenly raises ``ValueError``: the gather-based
``ref.reference`` oracle is never substituted in silence, and callers that
want it (the serving engine's default path, whose token identity needs its
bitwise dense-equal arithmetic) call it explicitly.

Layout: each grid step DMAs one whole physical page ``[bs, KV, hd]`` and
serves every q head of the lane from it, so a tile's last two dims are the
pool's own ``(KV, hd)`` — the per-head ``(1, hd)`` tile it replaced was
refused by the TPU compiler for any head count that is not a multiple of 8.
"""

from __future__ import annotations

from functools import partial

import jax

from .paged_attention import paged_attention_fwd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("logit_softcap", "window", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, context_lens, *,
                    logit_softcap=0.0, window=0, interpret=None):
    """Single-token decode attention through a block table.

    q: [B, H, hd]; k_pages/v_pages: [n_pages, block_size, KV, hd];
    block_tables: [B, max_blocks]; context_lens: [B]; window: sliding-window
    width (0 = global). Returns [B, H, hd].
    """
    H, KV = q.shape[1], k_pages.shape[2]
    if H % KV:
        raise ValueError(
            f"paged_attention: {H} q heads do not group evenly over {KV} kv "
            f"heads (q {q.shape}, pages {k_pages.shape}); call "
            f"paged_attention.ref.reference for this shape")
    if interpret is None:
        interpret = not _on_tpu()
    return paged_attention_fwd(
        q, k_pages, v_pages, block_tables, context_lens,
        logit_softcap=logit_softcap, window=window, interpret=interpret)
