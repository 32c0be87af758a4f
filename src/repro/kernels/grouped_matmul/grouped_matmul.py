"""Grouped expert FFN over expert-sorted rows — Pallas TPU kernel.

For every row ``r`` of group ``g`` (the rows of ``x`` are sorted by group
and ``group_sizes`` counts each group's rows; one group per held expert):

    out[r] = (act(x[r] @ w_gate[l, g]) * (x[r] @ w_up[l, g])) @ w_down[l, g]

the three grouped matmuls of a SwiGLU (GeGLU) expert, fused.  The weights
are a layer scan's whole ``[layers, experts, ...]`` stacks and ``l`` the
layer: the kernel reads layer ``l``'s experts in place, where a slice of
the stack handed to a custom call would be copied out first.  Rows past
the last group (the pairs routed to experts this chip does not hold, and
the padding) belong to no group and are never computed; their output rows
are left unspecified.

* The rows are cut into ``tm``-row tiles.  A *visit* is one (tile, group)
  pair with rows of that group in that tile; visits run in group order,
  so a tile that two groups share is visited twice in a row and every
  group's tiles are consecutive.  The grid is (visit, ff tile): its first
  dimension is the traced number of visits, so tiles that hold only
  unheld rows are not in it; the second walks the expert's hidden width
  in ``tf``-column tiles, accumulating the down projection in a float32
  VMEM scratch.
* The layer and the visit tables (group offsets, each visit's group and
  tile) are scalar-prefetch operands: the weight BlockSpecs read the layer
  and the visit's group and DMA that expert's ``[d, tf]`` / ``[tf, d]``
  slices; consecutive
  visits of one group re-read its weights only across tiles of a group
  that spans several, and a tile's rows are fetched once per visit.
* The output tile stays in VMEM across the visits that share it: the
  first starts from zero, each stores only its own group's rows.

The MXU accumulates in float32; the hidden activations are rounded to
``x``'s type before the down projection, as an unfused bf16 expert rounds
its matmul outputs.  The pallas_call carries the name ``grouped_matmul``,
which the compiled program gives the custom call and a device trace gives
its op.  Validated on CPU with interpret=True against ``ref.grouped_ffn``
(tests/test_kernels_grouped_matmul.py), compiled for v5e in
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

KERNEL_NAME = "grouped_matmul"


def visit_tables(group_sizes: jax.Array, m: int, tm: int) -> tuple:
    """(offsets [G + 1], group of each visit [V], tile of each visit [V],
    number of visits) for ``m`` rows in ``tm``-row tiles; V = m / tm + G
    - 1 bounds the visits (each group boundary inside a tile adds one)."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    V = m // tm + G - 1
    gids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), n_tiles,
                      total_repeat_length=V)
    begin = jnp.cumsum(n_tiles) - n_tiles
    tids = first[gids] + jnp.arange(V, dtype=jnp.int32) - begin[gids]
    tids = jnp.clip(tids, 0, m // tm - 1)
    return offsets, gids, tids, jnp.sum(n_tiles)


def _kernel(layer_ref, offsets_ref, gids_ref, tids_ref, x_ref, wg_ref, wu_ref,
            wd_ref, out_ref, acc_ref, *, tm: int, n_f: int, act):
    v, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    hidden = (act(gate) * up).astype(x.dtype)
    acc_ref[...] += jnp.dot(hidden, wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(f == n_f - 1)
    def _store():
        g, t = gids_ref[v], tids_ref[v]
        first = (v == 0) | (t != tids_ref[jnp.maximum(v - 1, 0)])
        before = jnp.where(first, jnp.zeros_like(out_ref), out_ref[...])
        rows = t * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...].astype(out_ref.dtype),
                                 before)


def ff_tile(ff: int) -> int:
    """Hidden-width tile: the widest of 512, 256, 128 dividing ``ff``,
    else the whole width."""
    return next((t for t in (512, 256, 128) if ff % t == 0), ff)


def grouped_ffn_fwd(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                    w_down: jax.Array, group_sizes: jax.Array, layer, *,
                    tm: int, act=jax.nn.silu,
                    interpret: bool = False) -> jax.Array:
    """x: [m, d] rows sorted by group, m a multiple of ``tm``; w_gate,
    w_up: [L, G, d, ff]; w_down: [L, G, ff, d]; group_sizes: [G] int32
    with sum <= m; layer: int32 scalar, the stack's layer used.  Returns
    [m, d]."""
    m, d = x.shape
    _, G, _, ff = w_gate.shape
    assert m % tm == 0, (m, tm)
    tf = ff_tile(ff)
    n_f = ff // tf
    offsets, gids, tids, visits = visit_tables(group_sizes, m, tm)
    up = lambda v, f, lyr, off, g, t: (lyr[0], g[v], 0, f)
    down = lambda v, f, lyr, off, g, t: (lyr[0], g[v], f, 0)
    rows = lambda v, f, lyr, off, g, t: (t[v], 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,       # layer, offsets, visit groups, tiles
        grid=(visits, n_f),
        in_specs=[
            pl.BlockSpec((tm, d), rows),
            pl.BlockSpec((None, None, d, tf), up),
            pl.BlockSpec((None, None, d, tf), up),
            pl.BlockSpec((None, None, tf, d), down),
        ],
        out_specs=pl.BlockSpec((tm, d), rows),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, n_f=n_f, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=KERNEL_NAME,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), offsets, gids, tids, x,
      w_gate, w_up, w_down)
