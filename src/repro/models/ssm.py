"""Mamba-2 SSD layer (state-space duality, arXiv:2405.21060).

Training/prefill uses the chunked dual form (quadratic intra-chunk attention
+ linear inter-chunk state recurrence); decode is the O(1) recurrent step.
``ngroups=1``: B/C projections are shared across SSD heads (the 370M config).

The chunked core here is the pure-jnp reference mirrored by the Pallas kernel
in ``repro.kernels.ssd_scan`` (selected with ``impl="pallas"``).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .blocks import dense_init, rms_norm
from .config import ModelConfig


def init_ssd(key, cfg: ModelConfig, dtype) -> dict:
    di, ns, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    ks = jax.random.split(key, 4)
    kz = jax.random.split(ks[0], 3)
    return {
        "ln": jnp.zeros((cfg.d_model,), dtype),
        # separate in-projections (shardable on the inner/model axis)
        "w_z": dense_init(kz[0], cfg.d_model, di, dtype),
        "w_xbc": dense_init(kz[1], cfg.d_model, di + 2 * ns, dtype),
        "w_dt": dense_init(kz[2], cfg.d_model, nh, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.d_conv, di + 2 * ns),
                                     jnp.float32) / math.sqrt(cfg.d_conv)).astype(dtype),
        "A_log": jnp.zeros((nh,), jnp.float32),       # A = -exp(A_log) ~ -1
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "out_ln": jnp.zeros((di,), dtype),
        "w_out": dense_init(ks[2], di, cfg.d_model, dtype),
    }


def init_ssd_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    di, ns, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, di + 2 * ns), dtype),
        "state": jnp.zeros((batch, nh, hd, ns), jnp.float32),
    }


def _causal_conv(x: jax.Array, w: jax.Array,
                 state: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv1d. x: [B, S, C], w: [K, C]."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    return jax.nn.silu(out)


def _ssd_chunked_core(xs, dt, A, B_mat, C_mat, D, chunk: int,
                      init_state: Optional[jax.Array] = None):
    """Chunked SSD. xs: [B,S,nh,hd], dt: [B,S,nh] (post-softplus),
    A: [nh] (negative), B_mat/C_mat: [B,S,ns]. Returns (y, final_state)."""
    Bb, S, nh, hd = xs.shape
    ns = B_mat.shape[-1]
    L = min(chunk, S)
    while S % L:  # largest chunk <= requested that divides S
        L -= 1
    N = S // L

    xs_f = xs.astype(jnp.float32).reshape(Bb, N, L, nh, hd)
    dt_c = dt.reshape(Bb, N, L, nh)
    Bc = B_mat.astype(jnp.float32).reshape(Bb, N, L, ns)
    Cc = C_mat.astype(jnp.float32).reshape(Bb, N, L, ns)

    dA = dt_c * A  # [B,N,L,nh] log-decay per step
    seg = jnp.cumsum(dA, axis=2)                       # within-chunk cumulative
    total = seg[:, :, -1]                              # [B,N,nh]

    # intra-chunk: M[i,j] = C_i.B_j * exp(seg_i - seg_j) * dt_j   (j <= i)
    G = jnp.einsum("bnis,bnjs->bnij", Cc, Bc)          # shared across heads
    decay = jnp.exp(seg[:, :, :, None, :] - seg[:, :, None, :, :])  # [B,N,i,j,nh]
    mask = jnp.tril(jnp.ones((L, L), bool))
    M = G[..., None] * jnp.where(mask[None, None, :, :, None], decay, 0.0) \
        * dt_c[:, :, None, :, :]                       # [B,N,i,j,nh]
    y_intra = jnp.einsum("bnijh,bnjhp->bnihp", M, xs_f)

    # chunk states: S_n = sum_j exp(total - seg_j) dt_j B_j (x) x_j
    w = jnp.exp(total[:, :, None, :] - seg) * dt_c     # [B,N,L,nh]
    states = jnp.einsum("bnjs,bnjh,bnjhp->bnhps", Bc, w, xs_f)  # [B,N,nh,hd,ns]

    # inter-chunk recurrence h_n = exp(total_n) h_{n-1} + S_n  (scan over N)
    def step(h, inp):
        s_n, tot_n = inp
        h_prev = h
        h = jnp.exp(tot_n)[:, :, None, None] * h + s_n
        return h, h_prev

    h0 = (jnp.zeros((Bb, nh, hd, ns), jnp.float32) if init_state is None
          else init_state)
    final, h_prevs = lax.scan(step, h0, (states.swapaxes(0, 1),
                                         total.swapaxes(0, 1)))
    h_prevs = h_prevs.swapaxes(0, 1)                   # [B,N,nh,hd,ns]

    # inter-chunk output: y_i += exp(seg_i) * C_i . h_{prev}
    y_inter = jnp.einsum("bnis,bnih,bnhps->bnihp",
                         Cc, jnp.exp(seg), h_prevs)
    y = (y_intra + y_inter).reshape(Bb, S, nh, hd)
    y = y + D[None, None, :, None] * xs.astype(jnp.float32)
    return y, final


def ssd_layer(cfg: ModelConfig, p: dict, x: jax.Array, *,
              cache: Optional[dict] = None, impl: str = "chunked",
              valid_len=None) -> tuple[jax.Array, Optional[dict]]:
    """Full Mamba-2 block: in_proj -> conv -> SSD -> gated norm -> out_proj.

    Prefill with a cache *continues* from the cache's recurrent/conv state
    (zeros for a fresh cache), so a prompt can be processed in chunks with
    the state carried across chunk calls.  ``valid_len`` (prefill only)
    freezes the recurrence past that many rows: padded tail rows (bucketed
    prefill, final prefill chunks) set dt = 0, so they neither decay nor
    feed the state, and the conv tail is read from the last real rows.
    """
    B, S, D = x.shape
    di, ns, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    z = h @ p["w_z"]
    xBC = h @ p["w_xbc"]
    dt_raw = h @ p["w_dt"]

    if cache is not None and S == 1:
        return _ssd_decode(cfg, p, x, z, xBC, dt_raw, cache)

    new_cache = None
    xBC_raw = xBC
    conv_state = cache["conv"] if cache is not None else None
    init_state = cache["state"] if cache is not None else None
    xBC = _causal_conv(xBC, p["conv_w"], state=conv_state)
    xs, B_mat, C_mat = jnp.split(xBC, [di, di + ns], axis=-1)
    xs = xs.reshape(B, S, nh, hd)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    if valid_len is not None:
        dt = jnp.where(jnp.arange(S)[None, :, None] < valid_len, dt, 0.0)
    A = -jnp.exp(p["A_log"])

    if impl == "pallas":
        if init_state is not None:
            # the Pallas scan always starts from a zero state
            raise ValueError(
                "ssd_layer: impl='pallas' cannot continue from a carried "
                "state (prefill with a cache); use impl='chunked'")
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, final_state = ssd_ops.ssd_scan(xs, dt, A, B_mat, C_mat, p["D"],
                                          chunk=cfg.ssm_chunk)
    else:
        # chunk-carried prefill threads the previous chunks' state in
        y, final_state = _ssd_chunked_core(xs, dt, A, B_mat, C_mat, p["D"],
                                           cfg.ssm_chunk,
                                           init_state=init_state)

    y = y.reshape(B, S, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["out_ln"], cfg.norm_eps)
    out = y @ p["w_out"]

    if cache is not None:  # prefill cache: raw-conv-input tail + final state
        pad = cfg.d_conv - 1
        full = jnp.concatenate([conv_state.astype(x.dtype), xBC_raw], axis=1)
        if valid_len is None:
            conv_tail = full[:, -pad:]
        else:  # last `pad` REAL rows: positions [valid_len - pad, valid_len)
            conv_tail = lax.dynamic_slice_in_dim(full, valid_len, pad, axis=1)
        new_cache = {"conv": conv_tail, "state": final_state}
    return x + out, new_cache


def _ssd_decode(cfg, p, x, z, xBC, dt_raw, cache):
    """Single-token recurrent step."""
    B = x.shape[0]
    di, ns, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    conv_in = jnp.concatenate([cache["conv"].astype(x.dtype), xBC], axis=1)
    new_conv = conv_in[:, 1:]
    xBC_t = jax.nn.silu(jnp.einsum("bkc,kc->bc", conv_in, p["conv_w"]))
    xs, B_mat, C_mat = jnp.split(xBC_t, [di, di + ns], axis=-1)
    xs = xs.reshape(B, nh, hd).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])  # [B,nh]
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)                                    # [B,nh]
    Bf = B_mat.astype(jnp.float32)
    Cf = C_mat.astype(jnp.float32)
    state = cache["state"] * dA[:, :, None, None] + \
        jnp.einsum("bh,bs,bhp->bhps", dt, Bf, xs)
    y = jnp.einsum("bs,bhps->bhp", Cf, state) + p["D"][None, :, None] * xs
    y = y.reshape(B, 1, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["out_ln"], cfg.norm_eps)
    out = y @ p["w_out"]
    return x + out, {"conv": new_conv, "state": state}
