"""Mamba-2 SSD chunked scan — Pallas TPU kernel (arXiv:2405.21060, §6).

Grid = (batch, heads, chunks); the chunk dimension is innermost and
sequential on TPU, so the running inter-chunk state h [hd, ns] lives in VMEM
scratch and carries across chunk steps for a fixed (b, head) — the same
sequential-grid-carry idiom as the flash-attention kv loop.

Per grid step, for chunk n of head h (L = chunk length):
    seg   = cumsum(dt * A)                          [L]      (masked sums)
    G     = C @ B^T                                 [L, L]   (MXU)
    M     = G * tril(exp(seg_i - seg_j)) * dt_j     [L, L]
    y     = M @ x  +  exp(seg) * (C @ h^T)  +  D*x  [L, hd]  (MXU x2)
    h     = exp(seg_L) * h + (w*x)^T @ B            [hd, ns] (MXU)

VMEM at L = 256, hd = 64, ns = 128 (the 370M config): x/y 64 KiB, B/C
128 KiB, M 256 KiB f32, h 32 KiB — well inside budget. B/C are shared
across heads (ngroups = 1), expressed by an index_map that ignores h.
x/y run head-major ([B, nh, S, hd]) and dt arrives both as a column and as
a row per chunk, so the cumsum is two masked reductions and no tile needs
an in-kernel transpose; the per-head scalars A and D are read from SMEM.

The final state per (b, head) is emitted to a second output at the last
chunk (used by prefill to seed decode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(a_ref, d_ref, x_ref, dtc_ref, dtr_ref, b_ref, c_ref,
            y_ref, state_ref, h_ref, *, n_chunks: int):
    head = pl.program_id(1)
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[head]                             # scalar A (negative), SMEM
    D = d_ref[head]
    x = x_ref[0, 0].astype(jnp.float32)         # [L, hd]
    dt_c = dtc_ref[0, 0].astype(jnp.float32)    # [L, 1]
    dt_r = dtr_ref[0, 0].astype(jnp.float32)    # [1, L]
    Bm = b_ref[0].astype(jnp.float32)           # [L, ns]
    Cm = c_ref[0].astype(jnp.float32)           # [L, ns]
    L = x.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)

    # inclusive cumsum of dt*A as a column and as a row (masked sums: the
    # decay matrix needs seg_i - seg_j without an in-kernel transpose)
    seg_c = jnp.sum(jnp.where(jj <= ii, dt_r * A, 0.0), axis=1,
                    keepdims=True)              # [L, 1]
    seg_r = jnp.sum(jnp.where(ii <= jj, dt_c * A, 0.0), axis=0,
                    keepdims=True)              # [1, L]
    total = jnp.sum(dt_r * A, axis=1, keepdims=True)   # [1, 1]

    # intra-chunk (dual / attention-like form)
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [L, L]
    M = jnp.where(ii >= jj, G * jnp.exp(seg_c - seg_r) * dt_r, 0.0)
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [L, hd]

    # inter-chunk contribution from the carried state
    h = h_ref[...]                              # [hd, ns]
    y += jnp.exp(seg_c) * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # [L, hd]
    y += D * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h' = exp(total) h + (w*x)^T B
    w = jnp.exp(total - seg_c) * dt_c           # [L, 1]
    h_ref[...] = jnp.exp(total) * h + jax.lax.dot_general(
        x * w, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # [hd, ns]

    @pl.when(n == n_chunks - 1)
    def _emit_state():
        state_ref[0, 0] = h_ref[...]


def ssd_scan_fwd(xs, dt, A, B_mat, C_mat, D, *, chunk: int = 256,
                 interpret: bool = False):
    """xs: [B,S,nh,hd]; dt: [B,S,nh]; A,D: [nh]; B_mat,C_mat: [B,S,ns].
    Returns (y [B,S,nh,hd] f32, state [B,nh,hd,ns] f32). S % chunk == 0."""
    Bb, S, nh, hd = xs.shape
    ns = B_mat.shape[-1]
    L = min(chunk, S)
    while S % L:
        L -= 1
    N = S // L

    # head-major x/y tiles [L, hd] and dt as both a column and a row, so
    # every tile's last two dims are legal for any head count; the per-head
    # scalars A and D sit whole in SMEM
    xt = xs.swapaxes(1, 2)                      # [B, nh, S, hd]
    dtt = dt.swapaxes(1, 2)                     # [B, nh, S]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_kernel, n_chunks=N)
    y, state = pl.pallas_call(
        kernel,
        grid=(Bb, nh, N),
        in_specs=[
            smem,                                                      # A
            smem,                                                      # D
            pl.BlockSpec((1, 1, L, hd), lambda b, h, n: (b, h, n, 0)),  # x
            pl.BlockSpec((1, 1, L, 1), lambda b, h, n: (b, h, n, 0)),   # dt col
            pl.BlockSpec((1, 1, 1, L), lambda b, h, n: (b, h, 0, n)),   # dt row
            pl.BlockSpec((1, L, ns), lambda b, h, n: (b, n, 0)),       # B
            pl.BlockSpec((1, L, ns), lambda b, h, n: (b, n, 0)),       # C
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, hd), lambda b, h, n: (b, h, n, 0)),
            pl.BlockSpec((1, 1, hd, ns), lambda b, h, n: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, nh, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((Bb, nh, hd, ns), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ns), jnp.float32)],
        interpret=interpret,
    )(A.astype(jnp.float32), D.astype(jnp.float32), xt,
      dtt[..., None], dtt[:, :, None, :], B_mat, C_mat)
    return y.swapaxes(1, 2), state
