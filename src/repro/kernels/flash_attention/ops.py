"""Jit'd public wrapper for the flash-attention kernel.

On TPU the Pallas kernel runs natively; elsewhere it runs in interpret mode
(the kernel body executes on CPU — used by the correctness sweeps). Shapes
that do not tile evenly raise ``ValueError``; the jnp oracle
``ref.reference`` is never substituted in silence.

Layout: the kernel runs on head-major ``[B, H, S, hd]`` views, so each tile
is ``(rows, hd)`` with whole-array last dims; the ``(1, hd)``-per-head tiles
of the ``[B, S, H, hd]`` layout were refused by the TPU compiler.
"""

from __future__ import annotations

from functools import partial

import jax

from .flash_attention import flash_attention_fwd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "logit_softcap",
                                   "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, q_positions, k_positions, causal=True,
                    window=0, logit_softcap=0.0, block_q=128, block_k=128,
                    interpret=None):
    Sq, H = q.shape[1], q.shape[2]
    Skv = k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    if Sq % bq or Skv % bk or H % k.shape[2]:
        raise ValueError(
            f"flash_attention: q {q.shape} / kv {k.shape} do not tile into "
            f"blocks ({bq}, {bk}) with whole head groups; call "
            f"flash_attention.ref.reference for this shape")
    if interpret is None:
        interpret = not _on_tpu()
    return flash_attention_fwd(
        q, k, v, q_positions, k_positions, causal=causal, window=window,
        logit_softcap=logit_softcap, block_q=bq, block_k=bk,
        interpret=interpret)
