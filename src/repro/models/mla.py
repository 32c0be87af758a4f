"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

The KV cache stores only the compressed latent c_kv [kv_lora] plus the shared
RoPE key k_rope [qk_rope_dim]. Decode uses the *absorbed* form: W_uk folds
into the query and W_uv into the output projection, so attention runs in the
latent space (MQA with one 'head' of width kv_lora + rope per query head).
A YaRN-scaled rope (``cfg.rope_yarn``) changes the rope frequencies and
multiplies the softmax scale by the YaRN mscale squared (``softmax_scale``).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .blocks import apply_rope, dense_init, rms_norm, attention
from .config import ModelConfig, yarn_mscale


def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    d, nh = cfg.d_model, cfg.n_heads
    qk = cfg.qk_rope_dim + cfg.qk_nope_dim
    ks = jax.random.split(key, 6)
    return {
        "ln": jnp.zeros((d,), dtype),
        "wq": dense_init(ks[0], d, nh * qk, dtype),
        "wkv_down": dense_init(ks[1], d, cfg.kv_lora_rank + cfg.qk_rope_dim, dtype),
        "kv_ln": jnp.zeros((cfg.kv_lora_rank,), dtype),
        "wk_up": dense_init(ks[2], cfg.kv_lora_rank, nh * cfg.qk_nope_dim, dtype),
        "wv_up": dense_init(ks[3], cfg.kv_lora_rank, nh * cfg.v_head_dim, dtype),
        "wo": dense_init(ks[4], nh * cfg.v_head_dim, d, dtype),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, kv_len: int, dtype) -> dict:
    return {
        "ckv": jnp.zeros((batch, kv_len, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, kv_len, cfg.qk_rope_dim), dtype),
        "pos": jnp.full((kv_len,), -1, jnp.int32),
    }


LANES = 128


def krope_width(cfg: ModelConfig) -> int:
    """Width of a rope-key pool row: ``qk_rope_dim`` rounded up to whole
    128-lane rows.  A narrower minor dim makes the TPU lay the stacked pool
    out page-minor, and every step would copy the whole pool into row
    order and back; the padding columns stay zero."""
    return -(-cfg.qk_rope_dim // LANES) * LANES


def pad_krope(cfg: ModelConfig, rows: jax.Array) -> jax.Array:
    """Rope-key rows [..., qk_rope_dim] -> pool rows [..., krope_width]."""
    pad = krope_width(cfg) - rows.shape[-1]
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])


def init_paged_mla_cache(cfg: ModelConfig, n_pages: int, block_size: int,
                         dtype) -> dict:
    """Physical block-pool cache for one MLA layer: the compressed latent
    (ckv) and shared rope key are per-token rows exactly like attention K/V,
    so they page through the same global block tables; a rope-key row is
    padded to ``krope_width``.  ``n_pages`` includes the trailing
    null/scratch page."""
    return {
        "ckv_pages": jnp.zeros((n_pages, block_size, cfg.kv_lora_rank), dtype),
        "krope_pages": jnp.zeros((n_pages, block_size, krope_width(cfg)),
                                 dtype),
    }


def softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope) ** -0.5, times mscale(factor, mscale_all_dim)^2
    under YaRN (DeepSeek-V2's published attention scale)."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    y = cfg.rope_yarn
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _project(cfg: ModelConfig, p: dict, h: jax.Array, positions: jax.Array):
    """Shared projections. Returns (q_nope, q_rope, ckv, krope)."""
    B, S, _ = h.shape
    nh = cfg.n_heads
    q = (h @ p["wq"]).reshape(B, S, nh, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_yarn)
    down = h @ p["wkv_down"]
    ckv, krope = jnp.split(down, [cfg.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_ln"], cfg.norm_eps)
    krope = apply_rope(krope[:, :, None, :], positions, cfg.rope_theta,
                       cfg.rope_yarn)[:, :, 0]
    return q_nope, q_rope, ckv, krope


def mla_layer(cfg: ModelConfig, p: dict, x: jax.Array, *,
              positions: jax.Array, cache: Optional[dict] = None,
              impl: str = "chunked", unroll: bool = False,
              paged_tables: Optional[jax.Array] = None, layer=None,
              shard_fn=None) -> tuple[jax.Array, Optional[dict]]:
    B, S, D = x.shape
    nh = cfg.n_heads
    h = rms_norm(x, p["ln"], cfg.norm_eps)

    if cache is not None and "ckv_pages" in cache:  # physical paged latents
        assert paged_tables is not None, "paged MLA cache needs block tables"
        assert layer is not None, "paged MLA cache needs the layer's index"
        return _mla_paged(cfg, p, x, h, positions, cache, paged_tables,
                          layer)

    if cache is not None and S == 1:
        return _mla_decode(cfg, p, x, h, positions, cache)

    sf = shard_fn or (lambda a, kind: a)
    q_nope, q_rope, ckv, krope = _project(cfg, p, h, positions)
    k_nope = (ckv @ p["wk_up"]).reshape(B, S, nh, cfg.qk_nope_dim)
    v = sf((ckv @ p["wv_up"]).reshape(B, S, nh, cfg.v_head_dim), "kv_heads")
    k_rope_b = jnp.broadcast_to(krope[:, :, None, :],
                                (B, S, nh, cfg.qk_rope_dim))
    q = sf(jnp.concatenate([q_nope, q_rope], axis=-1), "q_heads")
    k = sf(jnp.concatenate([k_nope, k_rope_b], axis=-1), "kv_heads")
    o = attention(q, k, v, q_positions=positions, k_positions=positions,
                  causal=True, impl=impl, unroll=unroll,
                  scale=softmax_scale(cfg))
    out = o.reshape(B, S, nh * cfg.v_head_dim) @ p["wo"]

    new_cache = None
    if cache is not None:  # prefill populates the latent cache
        size = cache["ckv"].shape[1]
        c = lax.dynamic_update_slice(cache["ckv"], ckv[:, -size:], (0, 0, 0))
        r = lax.dynamic_update_slice(cache["krope"], krope[:, -size:], (0, 0, 0))
        cp = lax.dynamic_update_slice(cache["pos"],
                                      positions[-size:].astype(jnp.int32), (0,))
        new_cache = {"ckv": c, "krope": r, "pos": cp}
    return x + out, new_cache


def _mla_paged(cfg, p, x, h, positions, cache, tables, layer):
    """Absorbed attention over block-table-paged latents.

    ``cache`` holds the segment's stacked ``[layers, n_pages, block_size,
    *]`` latent pools, carried through the layer scan; this layer writes
    its rows into, and gathers its pages from, index ``layer``.

    Two shapes, mirroring the paged attention layer: batched decode (x is
    [B, 1, D], ``positions`` = [B] per-lane absolute positions) and chunked
    prefill (x is [1, C, D], ``positions`` = [C] the chunk's rows).  The
    latent rows are written through the tables first, then the lane's
    logical view is gathered back in ascending position order — the same
    layout the dense cache stores (slot == position), so with the engine's
    ``kv_len == max_blocks * block_size`` guarantee the decode arithmetic
    is exactly ``_mla_decode``'s over identical operands.
    """
    from .blocks import paged_write

    B, S, _ = x.shape
    nh = cfg.n_heads
    if S == 1:  # batched decode: one token per lane, per-lane positions
        pos = positions.reshape(-1)                              # [B]
        q_nope, q_rope, ckv_t, krope_t = _project(cfg, p, h, pos[:, None])
        ctx = pos + 1                 # resident incl. the token just written
        q_pos = pos[:, None]                                     # [B, 1]
    else:       # chunk prefill: B == 1 lane, S == chunk rows
        pos = positions.reshape(-1)                              # [S]
        q_nope, q_rope, ckv_t, krope_t = _project(cfg, p, h, pos)
        ctx = pos[-1][None] + 1
        q_pos = pos[None]                                        # [1, S]
    ckv_pages, krope_pages = paged_write(
        cache["ckv_pages"], cache["krope_pages"], tables, pos, ckv_t,
        pad_krope(cfg, krope_t), layer)

    bs = ckv_pages.shape[2]
    L = tables.shape[1] * bs
    ckv_c = ckv_pages[layer, tables].reshape(B, L, cfg.kv_lora_rank)
    krope_c = krope_pages[layer, tables].reshape(
        B, L, krope_pages.shape[-1])[..., :cfg.qk_rope_dim]
    j = jnp.arange(L, dtype=jnp.int32)
    pos_c = jnp.where(j[None] < ctx[:, None], j[None], -1)       # [B, L]

    wk = p["wk_up"].reshape(cfg.kv_lora_rank, nh, cfg.qk_nope_dim)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk)
    scale = softmax_scale(cfg)
    scores = (jnp.einsum("bshr,bkr->bshk", q_lat, ckv_c) +
              jnp.einsum("bshd,bkd->bshk", q_rope, krope_c)).astype(jnp.float32)
    scores = scores * scale
    valid = (pos_c[:, None, :] >= 0) & \
        (pos_c[:, None, :] <= q_pos[:, :, None])                 # [B, S, L]
    scores = jnp.where(valid[:, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bshk,bkr->bshr", probs, ckv_c)
    wv = p["wv_up"].reshape(cfg.kv_lora_rank, nh, cfg.v_head_dim)
    o = jnp.einsum("bshr,rhd->bshd", o_lat, wv)
    out = o.reshape(B, S, nh * cfg.v_head_dim) @ p["wo"]
    return x + out, {"ckv_pages": ckv_pages, "krope_pages": krope_pages}


def _mla_decode(cfg, p, x, h, positions, cache):
    """Absorbed decode: attention in the latent space over the compressed cache."""
    B = x.shape[0]
    nh = cfg.n_heads
    pos = positions.reshape(())
    q_nope, q_rope, ckv_t, krope_t = _project(cfg, p, h, pos[None])

    slot = jnp.minimum(pos, cache["ckv"].shape[1] - 1)
    ckv_c = lax.dynamic_update_slice(cache["ckv"], ckv_t, (0, slot, 0))
    krope_c = lax.dynamic_update_slice(cache["krope"], krope_t, (0, slot, 0))
    pos_c = cache["pos"].at[slot].set(pos)

    # absorb W_uk: q_lat[h] = q_nope[h] @ W_uk[h]^T  -> [B, 1, nh, kv_lora]
    wk = p["wk_up"].reshape(cfg.kv_lora_rank, nh, cfg.qk_nope_dim)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk)
    scale = softmax_scale(cfg)
    scores = (jnp.einsum("bshr,bkr->bshk", q_lat, ckv_c) +
              jnp.einsum("bshd,bkd->bshk", q_rope, krope_c)).astype(jnp.float32)
    scores = scores * scale
    valid = (pos_c >= 0) & (pos_c <= pos)
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bshk,bkr->bshr", probs, ckv_c)   # [B,1,nh,kv_lora]
    # absorb W_uv into the output side
    wv = p["wv_up"].reshape(cfg.kv_lora_rank, nh, cfg.v_head_dim)
    o = jnp.einsum("bshr,rhd->bshd", o_lat, wv)
    out = o.reshape(B, 1, nh * cfg.v_head_dim) @ p["wo"]
    return x + out, {"ckv": ckv_c, "krope": krope_c, "pos": pos_c}
