"""Shared model blocks: norms, RoPE, attention (GQA / local / softcap), FFN, MoE.

All functions are pure; parameters are plain dicts of jnp arrays. Attention
has three implementations:

* ``naive``   — materializes [B, H, Sq, Skv] scores (small shapes, oracle),
* ``chunked`` — query-chunked online-softmax (memory-efficient; the default —
  it lowers on any backend and keeps dry-run memory realistic),
* ``pallas``  — the fused TPU kernel in ``repro.kernels.flash_attention``
  (interpret=True on CPU).

Conventions: q/k/v are [B, S, H, hd]; caches store post-RoPE keys; decode is
a single-token step with either a full-length cache (global attention) or a
rolling window cache (local / SWA) addressed at ``pos % window``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig, Yarn, yarn_mscale

DEFAULT_CHUNK = 1024


# =============================================================================
# initializers / norms / rope
# =============================================================================

def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with f32 internals and LOW-PRECISION boundary cotangents.

    The custom VJP keeps all math in f32 but returns d_x/d_scale in the
    input dtypes: without it, XLA threads f32 cotangents of the residual
    stream through every layer's collectives (2x wire + HBM bytes on the
    command-r train cell — EXPERIMENTS.md §Perf it.6).
    """
    return _rms_fwd(x, scale, eps)[0]


def _rms_fwd(x, scale, eps):
    xf = x.astype(jnp.float32)
    rstd = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    out = xf * rstd * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype), (x, scale, rstd)


def _rms_bwd(eps, res, g):
    x, scale, rstd = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    sf = 1.0 + scale.astype(jnp.float32)
    xhat = xf * rstd
    d_scale = jnp.sum(gf * xhat, axis=tuple(range(g.ndim - 1)))
    gx = gf * sf
    d_x = rstd * (gx - xhat * jnp.mean(gx * xhat, axis=-1, keepdims=True))
    return d_x.astype(x.dtype), d_scale.astype(scale.dtype)


rms_norm.defvjp(lambda x, scale, eps: _rms_fwd(x, scale, eps),
                _rms_bwd)


def rope_freqs(head_dim: int, theta: float,
               yarn: Optional[Yarn] = None) -> jax.Array:
    """Inverse rotary frequencies [head_dim / 2], computed at trace time.
    ``yarn``: DeepSeek-V2's YaRN table — pairs rotating fewer than
    ``beta_slow`` times over the original context are interpolated by
    ``factor``, pairs rotating more than ``beta_fast`` times are kept, and
    a linear ramp blends the correction range between."""
    extra = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if yarn is None:
        return extra

    def dim(rotations: float) -> float:
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), head_dim - 1)
    if high == low:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / yarn.factor * (1.0 - keep) + extra * keep


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn: Optional[Yarn] = None) -> jax.Array:
    """x: [B, S, H, hd]; positions: [S] or [B, S] absolute token positions.
    ``yarn`` scales the frequencies (``rope_freqs``) and cos/sin by the
    ratio of its two mscales (1 for DeepSeek-V2, whose two are equal)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta, yarn)                 # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    if angles.ndim == 2:                                # [S, hd/2] -> broadcast B
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


# =============================================================================
# attention core
# =============================================================================

def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """[B, S, KV, hd] -> [B, S, H, hd] by repeating each kv head H/KV times."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def _scores_mask(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
                 window: int) -> jax.Array:
    """[Sq, Skv] boolean validity from absolute positions (k_pos may be -1 =
    empty cache slot)."""
    m = k_pos[None, :] >= 0
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= k_pos[None, :] > q_pos[:, None] - window
    return m


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              q_positions: jax.Array, k_positions: jax.Array,
              causal: bool = True, window: int = 0,
              logit_softcap: float = 0.0, impl: str = "chunked",
              chunk: int = DEFAULT_CHUNK, unroll: bool = False,
              scale: Optional[float] = None) -> jax.Array:
    """Softmax attention with GQA, optional sliding window and logit softcap.

    q: [B, Sq, H, hd]; k, v: [B, Skv, KV, hd]. Positions are absolute.
    ``scale`` multiplies the logits (default ``hd ** -0.5``).
    """
    if impl == "pallas":
        assert scale is None, "the flash kernel scales by hd ** -0.5"
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            causal=causal, window=window, logit_softcap=logit_softcap)

    n_heads = q.shape[2]
    k = _expand_kv(k, n_heads)
    v = _expand_kv(v, n_heads)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    if impl == "naive" or q.shape[1] <= chunk:
        return _attn_block(q, k, v, q_positions, k_positions, scale,
                           causal, window, logit_softcap)
    assert impl == "chunked", impl
    B, Sq, H, hd = q.shape
    while Sq % chunk:  # largest chunk <= requested that divides Sq
        chunk -= 1
    n_chunks = Sq // chunk
    qc = q.reshape(B, n_chunks, chunk, H, hd).swapaxes(0, 1)
    pc = q_positions.reshape(n_chunks, chunk)

    @jax.checkpoint  # recompute scores in backward: O(chunk) attention memory
    def body(carry, xs):
        q_i, p_i = xs
        o = _attn_block(q_i, k, v, p_i, k_positions, scale, causal,
                        window, logit_softcap)
        return carry, o

    _, out = lax.scan(body, None, (qc, pc),
                      unroll=n_chunks if unroll else 1)
    # NB: output head dim follows V, not Q (MLA: v_head_dim != qk head dim)
    return out.swapaxes(0, 1).reshape(B, Sq, H, v.shape[-1])


def _attn_block(q, k, v, q_pos, k_pos, scale, causal, window, cap):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = softcap(scores, cap)
    mask = _scores_mask(q_pos, k_pos, causal=causal, window=window)
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# =============================================================================
# attention layer (projections + cache handling)
# =============================================================================

def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False) -> dict:
    ks = jax.random.split(key, 5)
    p = {
        "ln": jnp.zeros((cfg.d_model,), dtype),
        "wq": dense_init(ks[0], cfg.d_model, cfg.q_dim, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim, dtype),
        "wo": dense_init(ks[3], cfg.q_dim, cfg.d_model, dtype),
    }
    return p


def init_attn_cache(cfg: ModelConfig, batch: int, kv_len: int, local: bool,
                    dtype) -> dict:
    size = min(kv_len, cfg.window_size) if (local and cfg.window_size) else kv_len
    return {
        "k": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype),
        # absolute position held by each slot; -1 = empty
        "pos": jnp.full((size,), -1, jnp.int32),
    }


def init_paged_attn_cache(cfg: ModelConfig, n_pages: int, block_size: int,
                          dtype) -> dict:
    """Physical block-pool cache for one attention layer: K/V page pools
    ``[n_pages, block_size, n_kv_heads * head_dim]`` shared by every decode
    lane, addressed through per-lane block tables (``paged_tables``).  A
    row is lane-dense (its heads side by side); the ``[KV, hd]`` view is
    taken only after the gather, where in row-major order it is a bitcast.
    ``n_pages`` includes the trailing null/scratch page inactive lanes
    write into."""
    shape = (n_pages, block_size, cfg.n_kv_heads * cfg.head_dim)
    return {"k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype)}


def paged_write(k_pages: jax.Array, v_pages: jax.Array, tables: jax.Array,
                positions: jax.Array, k: jax.Array, v: jax.Array,
                layer) -> tuple:
    """Write per-token rows into layer ``layer`` of a pair of stacked page
    pools through block tables, in place.

    k_pages, v_pages: ``[layers, n_pages, block_size, width]`` — a scan
    segment's pools, carried whole through its layer scan; ``layer`` (may
    be traced) picks the layer written.  tables: [B, max_blocks];
    positions: [B] (decode: one row per lane) or [S] with B == 1 (chunk
    prefill: the chunk's rows for one lane); k, v: [B, S, ...] with
    B == len(positions) or S == len(positions), trailing dims flattening
    to the pool's width (attention K/V rows, MLA latent rows).  Decode
    writes each lane's row with its own ``dynamic_update_slice`` (B is
    static); a chunk writes its rows with one scatter.
    Rows whose table entry is the null page land in scratch (inactive lanes,
    padded chunk tails, window-ring entries already freed behind the
    window) — never read back, because reads are masked by ``context_lens``
    (and the window mask for ring layers).
    """
    bs = k_pages.shape[2]
    width = tables.shape[1]
    null = k_pages.shape[1] - 1                # scratch page, by convention
    blk = positions // bs
    safe = jnp.minimum(blk, width - 1)         # in-bounds for the gather only
    off = positions % bs
    decode = k.shape[0] == positions.shape[0]  # one row per lane
    if decode:
        phys = jnp.take_along_axis(tables, safe[:, None], axis=1)[:, 0]
    else:                                      # chunk prefill: B == 1
        phys = tables[0, safe]
    # positions past the table's reach (pad rows of a final chunk, runaway
    # inactive lanes) must go to scratch, not the clamped last real block
    phys = jnp.where(blk < width, phys, null)
    layer = jnp.asarray(layer, jnp.int32)
    out = []
    for pages, rows in ((k_pages, k), (v_pages, v)):
        rows = rows.reshape(-1, pages.shape[-1]).astype(pages.dtype)
        if decode:
            for b in range(rows.shape[0]):
                pages = lax.dynamic_update_slice(
                    pages, rows[b].reshape(1, 1, 1, -1),
                    (layer, phys[b], off[b], jnp.int32(0)))
        else:
            pages = pages.at[layer, phys, off].set(rows)
        out.append(pages)
    return tuple(out)


def _paged_write(cache: dict, tables: jax.Array, positions: jax.Array,
                 k: jax.Array, v: jax.Array, layer) -> dict:
    """``paged_write`` over an attention pool leaf ({"k_pages", "v_pages"})."""
    kp, vp = paged_write(cache["k_pages"], cache["v_pages"], tables,
                         positions, k, v, layer)
    return {"k_pages": kp, "v_pages": vp}


def attn_layer(cfg: ModelConfig, p: dict, x: jax.Array, *, local: bool,
               positions: jax.Array, cache: Optional[dict] = None,
               kv_override: Optional[tuple] = None, impl: str = "chunked",
               unroll: bool = False, paged_tables: Optional[jax.Array] = None,
               layer=None, valid_len=None,
               shard_fn=None) -> tuple[jax.Array, Optional[dict]]:
    """Pre-norm attention block. Returns (residual output, new cache).

    Training/prefill: ``positions`` = [S]; decode: x is [B, 1, D] and
    ``positions`` = [] scalar array of the current position; cache updated.
    ``kv_override`` (k, v, k_positions) implements cross-attention.
    ``valid_len`` (prefill only): tokens at positions >= valid_len are
    bucket padding — their rows must never displace real cache content.

    Paged mode (cache holds the segment's stacked ``k_pages``/``v_pages``
    pools, ``layer`` this layer's index into them, and ``paged_tables``
    carries [B, max_blocks] block tables): decode is a *batched* step — x
    is [B, 1, D] and ``positions`` = [B] per-lane absolute positions;
    prefill is a per-lane *chunk* — x is [1, C, D] and ``positions`` = [C]
    the chunk's absolute positions.  Both write K/V into the shared pools
    through the tables, in place, then attend through the gather-based
    paged kernel, which gathers this layer's pages from the stack.  Local
    (sliding-window) layers run the same path over their window block
    ring with the window mask excluding gathered rows behind
    ``q_pos - window`` (see docs/serving.md).
    """
    B, S, _ = x.shape
    window = cfg.window_size if local else 0
    sf = shard_fn or (lambda a, kind: a)
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = sf((h @ p["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim), "q_heads")

    if kv_override is not None:  # cross attention: kv precomputed from encoder
        k, v, k_pos = kv_override
        q_pos = positions.reshape(-1) if positions.ndim else positions[None]
        o = attention(q, k, v, q_positions=q_pos, k_positions=k_pos,
                      causal=False, window=0, impl=impl, unroll=unroll)
        out = sf(o, "heads").reshape(B, S, cfg.q_dim) @ p["wo"]
        return x + out, cache

    k = sf((h @ p["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim), "kv_heads")
    v = sf((h @ p["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim), "kv_heads")

    if cache is not None and "k_pages" in cache:  # physical paged cache
        assert paged_tables is not None, "paged cache needs block tables"
        assert layer is not None, "paged cache needs the layer's index"
        if S == 1:  # batched decode: one token per lane, per-lane positions
            pos = positions.reshape(-1)                       # [B]
            q = apply_rope(q, pos[:, None], cfg.rope_theta)
            k = apply_rope(k, pos[:, None], cfg.rope_theta)
            ctx = pos + 1                  # resident incl. the token just written
            q_pos = pos[:, None]
        else:       # chunk prefill: B == 1 lane, S == chunk rows
            pos = positions.reshape(-1)                       # [S]
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
            ctx = pos[-1][None] + 1
            q_pos = pos[None]
        new_cache = _paged_write(cache, paged_tables, pos, k, v, layer)
        if impl == "pallas" and S == 1:
            from repro.kernels.paged_attention import ops as pa_ops
            kp, vp = (lax.dynamic_index_in_dim(
                new_cache[n], layer, keepdims=False).reshape(
                    -1, new_cache[n].shape[2], cfg.n_kv_heads, cfg.head_dim)
                for n in ("k_pages", "v_pages"))
            o = pa_ops.paged_attention(
                q[:, 0], kp, vp, paged_tables, ctx,
                logit_softcap=cfg.attn_logit_softcap, window=window)[:, None]
        else:
            from repro.kernels.paged_attention import ref as pa_ref
            o = pa_ref.reference(
                q, new_cache["k_pages"], new_cache["v_pages"], paged_tables,
                ctx, q_positions=q_pos, layer=layer,
                logit_softcap=cfg.attn_logit_softcap, window=window)
        out = sf(o, "heads").reshape(B, S, cfg.q_dim) @ p["wo"]
        return x + out, new_cache

    if cache is None:  # training / prefill-without-cache
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attention(q, k, v, q_positions=positions, k_positions=positions,
                      causal=True, window=window,
                      logit_softcap=cfg.attn_logit_softcap, impl=impl,
                      unroll=unroll)
        new_cache = None
    elif S > 1:  # prefill WITH cache population
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attention(q, k, v, q_positions=positions, k_positions=positions,
                      causal=True, window=window,
                      logit_softcap=cfg.attn_logit_softcap, impl=impl,
                      unroll=unroll)
        new_cache = _prefill_cache(cache, k, v, positions, window, valid_len)
    else:  # decode step
        pos = positions.reshape(())  # scalar current position
        q = apply_rope(q, pos[None], cfg.rope_theta)
        k = apply_rope(k, pos[None], cfg.rope_theta)
        size = cache["k"].shape[1]
        slot = (pos % size) if window else jnp.minimum(pos, size - 1)
        ck = lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        cpos = cache["pos"].at[slot].set(pos)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        o = attention(q, ck, cv, q_positions=pos[None],
                      k_positions=cpos, causal=True, window=window,
                      logit_softcap=cfg.attn_logit_softcap, impl=impl)

    out = sf(o, "heads").reshape(B, S, cfg.q_dim) @ p["wo"]
    return x + out, new_cache


def _prefill_cache(cache: dict, k, v, positions, window: int,
                   valid_len=None) -> dict:
    size = cache["k"].shape[1]
    S = k.shape[1]
    if not window or S <= size:
        # linear layout: bucket pads land in their own (fresh) slots, so
        # position masking alone (mask_cache_positions) invalidates them
        ck = lax.dynamic_update_slice(cache["k"], k[:, -size:], (0, 0, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v[:, -size:], (0, 0, 0, 0))
        cpos = lax.dynamic_update_slice(cache["pos"],
                                        positions[-size:].astype(jnp.int32), (0,))
        return {"k": ck, "v": cv, "pos": cpos}
    # rolling window: scatter the last `size` REAL tokens into pos % size
    # slots.  Without bucket padding those are simply the trailing rows;
    # with padding (valid_len) the real tail ends at valid_len, so slice it
    # out dynamically and keep old cache content where the slice still
    # overlaps pad rows (short prompts) — pad rows must never displace real
    # ring slots (a pad at position p aliases the slot of p - size).
    if valid_len is None:
        tail_k, tail_v = k[:, -size:], v[:, -size:]
        tail_pos = positions[-size:].astype(jnp.int32)
        slots = tail_pos % size
    else:
        start = jnp.clip(valid_len - size, 0, S - size)
        tail_k = lax.dynamic_slice_in_dim(k, start, size, axis=1)
        tail_v = lax.dynamic_slice_in_dim(v, start, size, axis=1)
        tail_pos = lax.dynamic_slice_in_dim(positions.astype(jnp.int32),
                                            start, size)
        slots = tail_pos % size
        keep = tail_pos < valid_len
        tail_k = jnp.where(keep[None, :, None, None], tail_k,
                           cache["k"][:, slots])
        tail_v = jnp.where(keep[None, :, None, None], tail_v,
                           cache["v"][:, slots])
        tail_pos = jnp.where(keep, tail_pos, cache["pos"][slots])
    ck = cache["k"].at[:, slots].set(tail_k)
    cv = cache["v"].at[:, slots].set(tail_v)
    cpos = cache["pos"].at[slots].set(tail_pos)
    return {"k": ck, "v": cv, "pos": cpos}


# =============================================================================
# FFN (SwiGLU / GeGLU) and MoE
# =============================================================================

def init_ffn(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None) -> dict:
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "ln": jnp.zeros((cfg.d_model,), dtype),
        "w_gate": dense_init(ks[0], cfg.d_model, d_ff, dtype),
        "w_up": dense_init(ks[1], cfg.d_model, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, cfg.d_model, dtype),
    }


def _act_fn(name: str):
    return jax.nn.gelu if name == "gelu" else jax.nn.silu


def ffn_layer(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    act = _act_fn(cfg.ffn_act)
    out = (act(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
    return x + out


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    """The router over all ``n_experts`` (float32) and the weights of the
    routed experts this device holds (``cfg.held_experts``), plus the
    shared experts."""
    ks = jax.random.split(key, 5)
    E, D, F = len(cfg.held_experts), cfg.d_model, cfg.d_ff_expert
    scale = 1.0 / math.sqrt(D)
    p = {
        "ln": jnp.zeros((D,), dtype),
        "router": dense_init(ks[0], D, cfg.n_experts, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, D, F), jnp.float32) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, D, F), jnp.float32) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, F, D), jnp.float32) / math.sqrt(F)).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_ffn(ks[4], cfg, dtype,
                               d_ff=cfg.n_shared_experts * cfg.d_ff_expert)
        del p["shared"]["ln"]  # shares the MoE pre-norm
    return p


def _route(cfg: ModelConfig, p: dict, flat: jax.Array) -> tuple:
    """Softmax over every expert's float32 router score, greedy top-k; the
    gates renormalised over the k only under ``cfg.norm_topk_prob``.
    Returns (probs [..., E], gates [..., k], experts [..., k])."""
    logits = jnp.matmul(flat.astype(jnp.float32), p["router"],
                        precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True),
                                         1e-9)
    return probs, gate_vals, gate_idx


def _aux_loss(cfg: ModelConfig, probs, gate_idx) -> jax.Array:
    """Switch-style load-balancing loss over every token."""
    E = cfg.n_experts
    lead = tuple(range(probs.ndim - 1))
    density = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], E), axis=lead)
    mean_prob = jnp.mean(probs, axis=lead)
    return E * jnp.sum(density * mean_prob) * cfg.router_aux_coef


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _grouped_ffn(act, rows, w_gate, w_up, w_down, sizes, layer):
    """The held experts' SwiGLU over expert-sorted rows: the Pallas
    grouped matmul where the program is lowered for a TPU, its jnp oracle
    elsewhere (the CPU runs no Mosaic kernel natively)."""
    from repro.kernels.grouped_matmul import ops, ref
    return lax.platform_dependent(
        rows, w_gate, w_up, w_down, sizes, layer,
        tpu=functools.partial(ops.grouped_ffn, act=act),
        default=functools.partial(ref.grouped_ffn, act=act))


def _moe_dropless(cfg: ModelConfig, p: dict, x: jax.Array,
                  layer=None) -> tuple:
    """Every (token, slot) pair whose expert this device holds, computed:
    the pairs are sorted by expert (pairs for experts held elsewhere after
    them, never computed), each held expert's rows go through its SwiGLU
    in one grouped matmul that reads its weights once, and ``gate x out``
    is summed back per token.  Returns (residual output,
    aux loss, load): load [B, S, held + 1] int32 counts each token's
    pairs per held expert, the last column those sent elsewhere.
    ``layer``: the experts' weights are a layer scan's whole stacks
    ``[layers, held, ...]`` and this layer's index into them; None: one
    layer's ``[held, ...]``."""
    B, S, D = x.shape
    k = cfg.experts_per_token
    held = cfg.held_experts
    Eh = len(held)
    T = B * S
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    flat = h.reshape(T, D)
    with jax.named_scope("moe.route"):
        probs, gate_vals, gate_idx = _route(cfg, p, flat)       # [T, k]
        local = gate_idx - held.start
        mine = (local >= 0) & (local < Eh)
        slot_expert = jnp.where(mine, local, Eh).reshape(T * k)
    with jax.named_scope("moe.dispatch"):
        order = jnp.argsort(slot_expert, stable=True)           # [T*k]
        sizes = jnp.bincount(slot_expert, length=Eh + 1).astype(jnp.int32)
        rows = flat[order // k]                                 # [T*k, D]
    with jax.named_scope("moe.experts"):
        weights = [p[w] for w in EXPERT_WEIGHTS]
        if layer is None:
            weights, layer = [w[None] for w in weights], 0
        y = _grouped_ffn(_act_fn(cfg.ffn_act), rows, *weights, sizes[:Eh],
                         jnp.asarray(layer, jnp.int32))         # [T*k, D]
    with jax.named_scope("moe.combine"):
        back = jnp.argsort(order)           # pair -> its row in sorted order
        y = y[back].reshape(T, k, D)
        # rows of experts held elsewhere were never computed: select, so
        # whatever they hold cannot reach the sum
        w = jnp.where(mine, gate_vals, 0.0)
        out = jnp.sum(jnp.where(mine[..., None], y.astype(jnp.float32), 0.0)
                      * w[..., None], axis=1).astype(x.dtype)
        out = out.reshape(B, S, D)
    if "shared" in p:
        sh, act = p["shared"], _act_fn(cfg.ffn_act)
        out = out + (act(h @ sh["w_gate"]) * (h @ sh["w_up"])) @ sh["w_down"]
    load = jax.nn.one_hot(slot_expert.reshape(T, k), Eh + 1,
                          dtype=jnp.int32).sum(1).reshape(B, S, Eh + 1)
    return x + out, _aux_loss(cfg, probs, gate_idx), load


def moe_layer(cfg: ModelConfig, p: dict, x: jax.Array, *,
              capacity_factor: float = 1.25, n_groups: int = 1,
              lossless: bool = False, layer=None) -> tuple:
    """Top-k MoE.  Returns (residual output, router aux loss, load).

    ``lossless`` (serving): the dropless grouped path over the held
    experts (``_moe_dropless``, reading stacked expert weights at
    ``layer`` where it is given), whose load counts each token's pairs per
    held expert.  Otherwise (training) capacity-bounded with grouped
    gather/scatter dispatch: tokens are split into ``n_groups`` dispatch
    groups (one per device shard at run time — the launcher passes mesh
    size); capacity is per group, so every intermediate is sharded along
    the group axis and nothing [T, E, C]-sized ever materializes globally
    (TPU 'dropped' MoE; see DESIGN.md).  It holds every expert and
    returns no load.
    """
    if lossless:
        return _moe_dropless(cfg, p, x, layer)
    if cfg.expert_shard != (0, 1):
        raise ValueError(f"{cfg.name}: the capacity-bounded MoE needs every "
                         f"expert; this config holds {cfg.expert_shard}")
    B, S, D = x.shape
    E, topk = cfg.n_experts, cfg.experts_per_token
    T = B * S
    G = n_groups if T % n_groups == 0 else 1
    Tg = T // G
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    flat = h.reshape(G, Tg, D)

    probs, gate_vals, gate_idx = _route(cfg, p, flat)          # [G, Tg, k]
    aux = _aux_loss(cfg, probs, gate_idx)

    capacity = max(1, int(Tg * topk * capacity_factor / E))
    # position of each (token, slot) within its expert, per group
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)      # [G, Tg, k, E]
    flat_oh = onehot.reshape(G, Tg * topk, E)
    pos_in_e = (jnp.cumsum(flat_oh, axis=1) - flat_oh).reshape(G, Tg, topk, E)
    pos = jnp.take_along_axis(
        pos_in_e, gate_idx[..., None], axis=-1)[..., 0]        # [G, Tg, k]
    keep = pos < capacity

    # scatter tokens into [G, E*C, D] (sentinel row E*C receives drops)
    dest = jnp.where(keep, gate_idx * capacity + pos, E * capacity)
    src = jnp.broadcast_to(flat[:, :, None, :], (G, Tg, topk, D)) \
        .reshape(G, Tg * topk, D)
    dispatched = jnp.zeros((G, E * capacity + 1, D), flat.dtype)
    dispatched = jax.vmap(lambda d, i, s: d.at[i].set(s))(
        dispatched, dest.reshape(G, Tg * topk), src)
    dispatched = dispatched[:, :-1].reshape(G, E, capacity, D)

    act = _act_fn(cfg.ffn_act)
    hidden = act(jnp.einsum("gecd,edf->gecf", dispatched, p["w_gate"])) * \
        jnp.einsum("gecd,edf->gecf", dispatched, p["w_up"])
    expert_out = jnp.einsum("gecf,efd->gecd", hidden, p["w_down"])

    # gather back and combine with gate weights
    flat_out = expert_out.reshape(G, E * capacity, D)
    flat_out = jnp.concatenate(
        [flat_out, jnp.zeros((G, 1, D), flat_out.dtype)], axis=1)
    gathered = jax.vmap(lambda f, i: f[i])(
        flat_out, dest.reshape(G, Tg * topk)).reshape(G, Tg, topk, D)
    combined = jnp.einsum("gtkd,gtk->gtd", gathered,
                          gate_vals.astype(flat.dtype) * keep.astype(flat.dtype))

    out = combined.reshape(B, S, D)
    if "shared" in p:
        sh = p["shared"]
        out = out + (act(h @ sh["w_gate"]) * (h @ sh["w_up"])) @ sh["w_down"]
    return x + out, aux, None
