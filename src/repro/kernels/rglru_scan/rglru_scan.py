"""RG-LRU gated linear recurrence — Pallas TPU kernel (Griffin,
arXiv:2402.19427).

h_t = a_t * h_{t-1} + b_t, elementwise over the channel dim. The op is
memory-bound (12 B/element moved for ~2 FLOPs), so the kernel's job is to
stream a/b through VMEM once and keep the cross-chunk state resident — the
HBM-roofline optimum — rather than materializing the log-depth
associative-scan tree XLA builds on the wide form.

Grid = (batch, channel_blocks, seq_chunks); seq is innermost/sequential with
the running state h [1, bw] in VMEM scratch (same carry idiom as the other
kernels). Within a chunk the recurrence walks aligned [8, bw] row tiles: a
fori_loop loads one tile of a and b, steps its 8 rows with static slices,
and stores the 8 states as one tile.

Block choice: bw = 128 lanes (v5e vector lane width), L = 256 rows ->
a/b tiles 128 KiB each in f32; state 512 B.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, state_ref, h_ref, *, n_chunks: int,
            chunk: int, rows: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def tile(i, h):                  # h: [1, bw]
        # one aligned [rows, bw] tile per load/store; the recurrence walks
        # its rows with static slices of the loaded value
        r = pl.multiple_of(i * rows, rows)
        a = a_ref[0, pl.ds(r, rows), :]
        b = b_ref[0, pl.ds(r, rows), :]
        hs = []
        for t in range(rows):
            h = a[t:t + 1] * h + b[t:t + 1]
            hs.append(h)
        o_ref[0, pl.ds(r, rows), :] = jnp.concatenate(hs, axis=0)
        return h

    h = jax.lax.fori_loop(0, chunk // rows, tile, h_ref[...])
    h_ref[...] = h

    @pl.when(n == n_chunks - 1)
    def _emit():
        state_ref[0] = h


def rglru_scan_fwd(a, bx, *, block_w: int = 128, chunk: int = 256,
                   interpret: bool = False):
    """a, bx: [B, S, W] f32 -> (hs [B, S, W] f32, h_final [B, W] f32)."""
    B, S, W = a.shape
    bw = min(block_w, W)
    while W % bw:
        bw -= 1
    L = min(chunk, S)
    while S % L:
        L -= 1
    N = S // L

    kernel = functools.partial(_kernel, n_chunks=N, chunk=L,
                               rows=math.gcd(L, 8))
    # the final state is emitted as [B, 1, W] so its tile is (1, bw)
    hs, h_fin = pl.pallas_call(
        kernel,
        grid=(B, W // bw, N),
        in_specs=[
            pl.BlockSpec((1, L, bw), lambda b, w, n: (b, n, w)),
            pl.BlockSpec((1, L, bw), lambda b, w, n: (b, n, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, bw), lambda b, w, n: (b, n, w)),
            pl.BlockSpec((1, 1, bw), lambda b, w, n: (b, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
    )(a, bx)
    return hs, h_fin[:, 0]
