"""Fused flash attention — Pallas TPU kernel.

TPU mapping of the FlashAttention online-softmax algorithm (arXiv:2205.14135)
with the variants this framework's architectures need fused in:

* GQA head mapping (q head -> kv head via BlockSpec index_map),
* position-based causal + sliding-window masking (gemma2 local, mixtral SWA),
* logit softcap (gemma2),
* f32 running max / sum / accumulator scratch in VMEM.

Grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is innermost
and sequential on TPU, so the m/l/acc scratch carries across kv steps for a
fixed (b, h, iq). The kernel runs on head-major ``[B, H, S, hd]`` views, so
q/o tiles are [bq, hd] and k/v tiles [bk, hd] with whole-array last dims
for any head count; q positions ride as a [bq, 1] column and k positions as
a [1, bk] row. bq = bk = 128 by default (MXU-aligned).

VMEM budget at bq = bk = 128, hd = 256, f32 scratch:
q/k/v/o tiles 4 x 128 x 256 x 2B = 256 KiB; acc 128 x 256 x 4B = 128 KiB;
s/p 128 x 128 x 4B = 64 KiB x 2 — comfortably inside the ~16 MiB/core VMEM.

Validated on CPU with interpret=True against ``ref.reference`` over a
shape/dtype/flag sweep (tests/test_kernels_flash_attention.py), compiled for
v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(q_pos_ref, k_pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref,
            *, scale: float, causal: bool, window: int,
            logit_softcap: float, n_kv_blocks: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                          # [bq, hd]
    k = k_ref[0, 0]                          # [bk, hd]
    v = v_ref[0, 0]
    q_pos = q_pos_ref[...]                   # [bq, 1]
    k_pos = k_pos_ref[...]                   # [1, bk]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # [bq, bk]
    if logit_softcap:
        s = logit_softcap * jnp.tanh(s / logit_softcap)

    mask = k_pos >= 0
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                      # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # fully-masked-so-far rows keep m = NEG_INF; make the rescale a no-op
    alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
    alpha = jnp.where(m_new == NEG_INF, 1.0, alpha)
    p = jnp.exp(s - jnp.where(m_new == NEG_INF, 0.0, m_new))
    p = jnp.where(mask, p, 0.0)

    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, q_positions, k_positions, *,
                        causal: bool = True, window: int = 0,
                        logit_softcap: float = 0.0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q: [B, Sq, H, hd]; k, v: [B, Skv, KV, hd]. Sq % bq == Skv % bk == 0."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    assert H % KV == 0, (H, KV)
    group = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        logit_softcap=logit_softcap, n_kv_blocks=nk)

    # head-major operands: every tile's last two dims are (rows, hd), so the
    # tile is legal for any head count; positions ride as a column (q) and a
    # row (k) so the mask is a plain broadcast
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((bq, 1), lambda b, h, iq, ik: (iq, 0)),       # q_pos
            pl.BlockSpec((1, bk), lambda b, h, iq, ik: (0, ik)),       # k_pos
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, h, iq, ik: (b, h, iq, 0)),           # q
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),  # k
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),  # v
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running sum
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q_positions.astype(jnp.int32).reshape(Sq, 1),
      k_positions.astype(jnp.int32).reshape(1, Skv), qt, kt, vt)
    return out.swapaxes(1, 2)
