"""Pure-jnp oracle for paged (block-table) attention.

Reconstructs each lane's logical KV sequence by gathering physical cache
blocks through its block table, then runs exactly the same masked-softmax
arithmetic as ``repro.models.blocks._attn_block``.  Because invalid rows
(beyond ``context_lens`` or failing the causal test) are forced to the same
-1e30 sentinel before the f32 softmax, their probabilities underflow to an
exact ``0.0`` — so the output is *bitwise identical* to dense attention over
the same resident tokens.  The continuous-batching engine relies on that for
token identity with the static ``Engine`` oracle.

Shape conventions (see docs/kernels.md):

* ``q``:            [B, Sq, H, hd]   (decode: Sq == 1; chunked prefill: Sq == chunk)
* ``k_pages/v_pages``: [n_pages, block_size, KV * hd] physical block pool,
  lane-dense (page ``n_pages - 1`` is conventionally the null/scratch
  block), or a layer scan's stacked ``[layers, n_pages, block_size,
  KV * hd]`` pools with ``layer`` picking one.  The ``[KV, hd]`` view is
  taken after the gather; a row of any shape that flattens to
  ``KV * hd`` reads the same, such as the Pallas kernel's
  ``[n_pages, block_size, KV, hd]``
* ``block_tables``: [B, max_blocks] int32 — logical block i of lane b lives
  in physical page ``block_tables[b, i]``
* ``context_lens``: [B] int32 — resident tokens per lane, *including* any
  token written this step
* ``q_positions``:  [B, Sq] absolute positions of the query tokens
* ``window``: sliding-window width (0 = global).  With a window, logical
  position ``j`` is additionally masked unless ``j > q_pos - window`` — the
  engine's window block rings rely on this to exclude gathered KV that is
  resident in a not-yet-freed block but already behind the window (and to
  neutralize the null-page rows left where freed-behind blocks used to be).

Verify-step length masking (speculative decoding): the causal term is
per-*row* (``j <= q_positions[b, i]``), so a multi-token verify pass over
``[x_t, d_1..d_k]`` scores row ``i`` against exactly the first
``q_positions[b, i] + 1`` resident tokens — never against the draft
pass's speculatively written rows at higher positions, and never against
stale rows a rewind left beyond the lane's position (they sit past every
later query's position until an accepted token overwrites them).  This is
what lets the engine rewind by table truncation alone, without zeroing
physical pages.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def reference(q, k_pages, v_pages, block_tables, context_lens, *,
              q_positions, logit_softcap=0.0, window=0, layer=None):
    """Gather-based paged attention. Returns [B, Sq, H, hd]."""
    B, Sq, H, hd = q.shape

    def gather(pages):
        # one gather straight from the (stacked) pool: [B, max_blocks, bs,
        # *row] -> [B, L, KV, hd], logical order 0..L-1
        g = pages[block_tables] if layer is None else \
            pages[layer, block_tables]
        return g.reshape(B, g.shape[1] * g.shape[2], -1, hd)

    k, v = gather(k_pages), gather(v_pages)
    L, n_kv = k.shape[1], k.shape[2]
    if n_kv != H:
        k = jnp.repeat(k, H // n_kv, axis=2)
        v = jnp.repeat(v, H // n_kv, axis=2)

    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    j = jnp.arange(L, dtype=jnp.int32)
    # resident (j < context_len) AND causal (j <= q_pos), per lane
    mask = (j[None, None, :] < context_lens[:, None, None]) & \
        (j[None, None, :] <= q_positions[:, :, None])          # [B, Sq, L]
    if window:
        mask &= j[None, None, :] > q_positions[:, :, None] - window
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
