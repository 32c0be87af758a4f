"""MLA decode attention over paged latents — Pallas TPU kernel.

For every lane ``b`` with ``lens[b] = n > 0`` and every head ``h``:

    s[j]  = scale * (q_lat[b, h] . ckv[j] + q_rope[b, h] . krope[j])
    o[b, h] = sum_{j < n} softmax(s)[j] * ckv[j]

where row ``j`` of the lane is row ``j % bs`` of page ``tables[b, j //
bs]`` of layer ``layer`` of the stacked pools.  This is the absorbed
(latent-space) attention of a DeepSeek-V2 decode step: every head reads
the same latent rows, so the scores of a block of rows are a real matmul
``q_lat [H, R] @ ckv [T, R]^T``.  A lane with ``lens[b] == 0`` reads
nothing and gets zeros.

* The pools are the layer scan's whole ``[layers, n_pages, bs, width]``
  stacks, left where they are (``memory_space=ANY``); the kernel DMAs
  the lane's pages of layer ``layer`` itself, so no slice of the stack is
  made.  The rope-key rows are read at the pool's padded width
  (``mla.krope_width``): ``q_rope`` comes padded with zeros, and the
  pool's padding columns are zero, so they add nothing.
* The grid is one step per lane.  A lane walks its ``cdiv(n, T)`` blocks
  of ``T = pages * bs`` rows in a loop with a traced trip count, so it
  stops at its own length: only the ``cdiv(n, bs)`` pages that hold its
  rows are read, and positions past ``n`` in its last block are masked.
  Each block's pages are DMAed row for row into one of two VMEM buffers
  while the other is computed on (double buffering).
* An online softmax keeps the running max, sum and ``[H, R]`` output in
  float32; the probabilities meet ``ckv`` in the pools' type, as the XLA
  path rounds its probabilities to the serve type.

The layer, the flattened tables and the lengths are scalar-prefetch
operands.  The pallas_call carries the name ``mla_decode``, which the
compiled program gives the custom call and a device trace gives its op.
Validated on CPU with interpret=True against ``ref.mla_decode``
(tests/test_kernels_mla_decode.py), compiled for v5e in
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

KERNEL_NAME = "mla_decode"
MASK = -1e30


def _kernel(layer_ref, tables_ref, lens_ref, q_ref, qr_ref, ckv_hbm, kr_hbm,
            o_ref, ckv_buf, kr_buf, sems, *, pages: int, width: int,
            scale: float):
    b = pl.program_id(0)
    layer = layer_ref[0]
    n = lens_ref[b]
    bs = ckv_hbm.shape[2]
    rows = pages * bs
    n_pages = (n + bs - 1) // bs
    n_blocks = (n_pages + pages - 1) // pages

    @pl.when(b == 0)
    def _clear():
        # rows of a last block past the lane's pages are masked, but
        # 0 * NaN is NaN in the probabilities' matmul: the buffers start
        # finite, and later hold only finite rows copied from the pools
        ckv_buf[...] = jnp.zeros(ckv_buf.shape, ckv_buf.dtype)
        kr_buf[...] = jnp.zeros(kr_buf.shape, kr_buf.dtype)

    def each_copy(blk, slot, act):
        """``act`` on the DMAs of block ``blk``'s live pages into buffer
        ``slot``; a last block's pages past the lane's length are not
        read."""
        for j in range(pages):
            i = blk * pages + j

            @pl.when(i < n_pages)
            def _page():
                page = tables_ref[b * width + i]
                dst = pl.ds(j * bs, bs)
                act(pltpu.make_async_copy(
                    ckv_hbm.at[layer, page], ckv_buf.at[slot, dst],
                    sems.at[0, slot]))
                act(pltpu.make_async_copy(
                    kr_hbm.at[layer, page], kr_buf.at[slot, dst],
                    sems.at[1, slot]))

    start = lambda c: c.start()
    wait = lambda c: c.wait()

    @pl.when(n_blocks > 0)
    def _first():
        each_copy(0, 0, start)

    q = q_ref[...]                                          # [H, R]
    qr = qr_ref[...]                                        # [H, Kw]
    heads = q.shape[0]
    nt = (((1,), (1,)), ((), ()))                           # a @ b.T

    def block(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _next():
            each_copy(blk + 1, 1 - slot, start)

        each_copy(blk, slot, wait)
        ckv = ckv_buf[slot]                                 # [T, R]
        s = (lax.dot_general(q, ckv, nt, preferred_element_type=jnp.float32)
             + lax.dot_general(qr, kr_buf[slot], nt,
                               preferred_element_type=jnp.float32)) * scale
        pos = blk * rows + lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
        s = jnp.where(pos < n, s, MASK)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(ckv.dtype), ckv,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((heads, 1), MASK, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros(q.shape, jnp.float32))
    _, l, acc = lax.fori_loop(0, n_blocks, block, init)
    # l >= 1 wherever a row was read; a lane that read none has acc == 0
    o_ref[...] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def mla_decode_fwd(q_lat: jax.Array, q_rope: jax.Array, ckv_pages: jax.Array,
                   krope_pages: jax.Array, tables: jax.Array, lens: jax.Array,
                   layer, *, scale: float, pages: int,
                   interpret: bool = False) -> jax.Array:
    """q_lat: [B, H, R]; q_rope: [B, H, Kw] padded with zeros to the rope
    pool's width; ckv_pages: [L, N, bs, R]; krope_pages: [L, N, bs, Kw];
    tables: [B, W] int32 page ids; lens: [B] int32, each at most W * bs;
    layer: int32 scalar.  ``pages`` pages make one compute block.  Returns
    o_lat [B, H, R] in q_lat's type."""
    B, H, R = q_lat.shape
    Kw = q_rope.shape[-1]
    bs = ckv_pages.shape[2]
    W = tables.shape[1]
    lane = lambda b, *_: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # layer, tables, lens
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, H, R), lane),
            pl.BlockSpec((None, H, Kw), lane),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, H, R), lane),
        scratch_shapes=[
            pltpu.VMEM((2, pages * bs, R), ckv_pages.dtype),
            pltpu.VMEM((2, pages * bs, Kw), krope_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, pages=pages, width=W, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), lens.astype(jnp.int32),
      q_lat, q_rope, ckv_pages, krope_pages)
