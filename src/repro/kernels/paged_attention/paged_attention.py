"""Paged decode attention — Pallas TPU kernel.

vLLM-style paged attention (single query token per lane against a
block-granular physical KV cache), with the block table doing the address
translation:

* Grid = (batch, kv_blocks); the kv-block dimension is innermost and
  sequential on TPU, so the online-softmax m/l/acc scratch carries across
  physical blocks for a fixed lane.
* The block table and context lengths are **scalar-prefetch** operands
  (``pltpu.PrefetchScalarGridSpec``): the k/v BlockSpec ``index_map`` reads
  ``tables[b, i]`` to DMA logical block i of lane b — one whole
  ``[block_size, KV, hd]`` page — from wherever it physically lives in the
  ``[n_pages, block_size, KV, hd]`` pool; the gather never materializes a
  dense per-lane KV view.
* Every q head of the lane is served from that page: q is laid out
  ``[B, group, KV, hd]`` (q head ``kv * group + g``), so each tile's last
  two dims are the pool's own ``(KV, hd)`` for any head count.  Tokens past
  ``context_lens[b]`` are masked to -1e30 inside the kernel, so padded
  table tails (null blocks) contribute exact zeros.

One query row per head would leave an MXU tile almost empty, so the
per-head dot products run on the VPU in f32; decode is bandwidth-bound on
the KV stream.  Validated on CPU with interpret=True against
``ref.reference`` (tests/test_kernels_paged_attention.py), compiled for
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


def _kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref,
            *, scale: float, block_size: int, logit_softcap: float,
            n_kv_blocks: int, window: int, group: int):
    b = pl.program_id(0)
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = k_ref[0].astype(jnp.float32)          # [bs, KV, hd]: one whole page
    v = v_ref[0].astype(jnp.float32)

    # token j of this physical block sits at logical position ib*bs + j;
    # only positions below the lane's context length are resident
    ctx = lens_ref[b]
    pos = ib * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (block_size, 1, 1), 0)
    mask = pos < ctx
    if window:
        # sliding window: the decode query sits at ctx - 1, so positions
        # at or below (ctx - 1) - window are behind the window — gathered
        # KV in not-yet-freed ring blocks (or null-page rows where freed
        # blocks used to be) must contribute exact zeros
        mask &= pos > ctx - 1 - window

    for g in range(group):                    # q heads kv*group + g
        q = q_ref[0, g].astype(jnp.float32)   # [KV, hd]
        # per-head dot products on the VPU: decode is one query row per
        # head, so an MXU tile would be almost all padding
        s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale  # [bs,KV,1]
        if logit_softcap:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[g]                     # [KV, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        # fully-masked-so-far heads keep m = NEG_INF; make the rescale a no-op
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        alpha = jnp.where(m_new == NEG_INF, 1.0, alpha)
        p = jnp.exp(s - jnp.where(m_new == NEG_INF, 0.0, m_new)[None])
        p = jnp.where(mask, p, 0.0)           # [bs, KV, 1]

        l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=0)
        acc_ref[g] = alpha * acc_ref[g] + jnp.sum(p * v, axis=0)  # [KV, hd]
        m_ref[g] = m_new

    @pl.when(ib == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention_fwd(q, k_pages, v_pages, block_tables, context_lens, *,
                        logit_softcap: float = 0.0, window: int = 0,
                        interpret: bool = False) -> jax.Array:
    """q: [B, H, hd]; k_pages/v_pages: [n_pages, bs, KV, hd];
    block_tables: [B, max_blocks]; context_lens: [B]; window: sliding-window
    width (0 = global attention). Returns [B, H, hd]."""
    B, H, hd = q.shape
    n_pages, bs, KV, _ = k_pages.shape
    assert H % KV == 0, (H, KV)
    group = H // KV
    max_blocks = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    # q head h = kv * group + g reads kv head kv: lay q out [B, group, KV, hd]
    # so every block's last two dims are (KV, hd), whole array dims
    qg = q.reshape(B, KV, group, hd).swapaxes(1, 2)

    kernel = functools.partial(
        _kernel, scale=scale, block_size=bs, logit_softcap=logit_softcap,
        n_kv_blocks=max_blocks, window=window, group=group)

    page = pl.BlockSpec((1, bs, KV, hd),
                        lambda b, ib, tables, lens: (tables[b, ib], 0, 0, 0))
    lane = pl.BlockSpec((1, group, KV, hd),
                        lambda b, ib, tables, lens: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # block_tables, context_lens
        grid=(B, max_blocks),
        in_specs=[lane, page, page],         # q, k, v
        out_specs=lane,
        scratch_shapes=[
            pltpu.VMEM((group, KV, 1), jnp.float32),    # running max
            pltpu.VMEM((group, KV, 1), jnp.float32),    # running sum
            pltpu.VMEM((group, KV, hd), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.swapaxes(1, 2).reshape(B, H, hd)
