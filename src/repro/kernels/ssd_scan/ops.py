"""Jit'd public wrapper for the SSD scan kernel.

On TPU the Pallas kernel runs natively; elsewhere it runs in interpret mode
(the kernel body executes on CPU — used by the correctness sweeps against
``ref.reference``).  xs: [B, S, nh, hd]; dt: [B, S, nh] (post-softplus);
A: [nh] (negative); B_mat/C_mat: [B, S, ns]; D: [nh].  Returns
(y [B, S, nh, hd], final inter-chunk state [B, nh, hd, ns]).  The scan
starts from a zero state; it has no seeded-state entry, and model code that
carries a state in must not ask for it (``ssm.ssd_layer`` raises).

Layout: the per-head scalars A and D sit whole in SMEM (their rank-1
``(1,)`` VMEM tiles were refused by the TPU compiler), x/y run head-major
``[B, nh, S, hd]``, and dt rides as a column and a row per chunk so the
in-kernel cumsum is two masked reductions.
"""

from __future__ import annotations

from functools import partial

import jax

from .ssd_scan import ssd_scan_fwd


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xs, dt, A, B_mat, C_mat, D, *, chunk: int = 256,
             interpret=None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return ssd_scan_fwd(xs, dt, A, B_mat, C_mat, D, chunk=chunk,
                        interpret=interpret)
