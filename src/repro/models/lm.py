"""Model assembly: params init + forward for every assigned architecture.

One code path covers dense / MoE / SSM / hybrid decoder-only LMs, VLM
(frontend-stub) variants, and the enc-dec (audio) family. Layers are grouped
into scan segments (``ModelConfig.segments()``): XLA compiles one body per
layer *class*, not per layer — critical for dry-run compile times at 42+
layers and 512 devices.

``forward(...)`` handles three modes:
  train    — full-sequence teacher forcing, remat'd scan bodies, aux losses;
  prefill  — full sequence, returns populated caches;
  decode   — one token per sequence against the cache.
Its third output (``Aux``) holds the router loss and, for a MoE model
served through the dropless path, each token's expert load.
"""

from __future__ import annotations

import hashlib
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import blocks, mla as mla_mod, rglru as rglru_mod, ssm as ssm_mod
from .blocks import rms_norm, softcap
from .config import LayerSpec, ModelConfig, Segment


# =============================================================================
# init
# =============================================================================

def _init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype,
                cross: bool, dense_ff: Optional[int] = None) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {}
    if spec.mixer in ("global", "local"):
        p["attn"] = blocks.init_attention(ks[0], cfg, dtype)
    elif spec.mixer == "mla":
        p["mla"] = mla_mod.init_mla(ks[0], cfg, dtype)
    elif spec.mixer == "ssd":
        p["ssd"] = ssm_mod.init_ssd(ks[0], cfg, dtype)
    elif spec.mixer == "rglru":
        p["rglru"] = rglru_mod.init_rglru(ks[0], cfg, dtype)
    if cross:
        p["xattn"] = blocks.init_attention(ks[1], cfg, dtype, cross=True)
    if spec.ffn == "dense":
        p["ffn"] = blocks.init_ffn(ks[2], cfg, dtype, d_ff=dense_ff or cfg.d_ff)
    elif spec.ffn == "moe":
        p["moe"] = blocks.init_moe(ks[2], cfg, dtype)
    return p


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> dict:
    keys = jax.random.split(key, 8 + len(cfg.segments()))
    params: dict = {
        "embed": (jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = blocks.dense_init(keys[1], cfg.d_model,
                                              cfg.padded_vocab, dtype)
    if cfg.frontend and not cfg.n_enc_layers:
        params["frontend_proj"] = blocks.dense_init(
            keys[2], cfg.frontend_dim, cfg.d_model, dtype)

    cross = bool(cfg.n_enc_layers)
    for si, seg in enumerate(cfg.segments()):
        seg_p = {}
        for ci, spec in enumerate(seg.cycle):
            lkeys = jax.random.split(jax.random.fold_in(keys[3], si * 16 + ci),
                                     seg.repeats)
            dense_ff = cfg.d_ff
            seg_p[f"c{ci}"] = jax.vmap(
                lambda k: _init_layer(k, cfg, spec, dtype, cross, dense_ff)
            )(lkeys)
        params[f"seg{si}"] = seg_p

    if cfg.n_enc_layers:
        params["enc_frontend"] = blocks.dense_init(
            keys[4], cfg.frontend_dim, cfg.d_model, dtype)
        ekeys = jax.random.split(keys[5], cfg.n_enc_layers)
        espec = LayerSpec("global", "dense")
        params["enc"] = jax.vmap(
            lambda k: _init_layer(k, cfg, espec, dtype, cross=False)
        )(ekeys)
        params["enc_final_norm"] = jnp.zeros((cfg.d_model,), dtype)
    return params


def init_cache(cfg: ModelConfig, batch: int, kv_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Decode/prefill cache mirroring the segment structure of the params."""
    def layer_cache(spec: LayerSpec) -> dict:
        c: dict = {}
        if spec.mixer in ("global", "local"):
            c["attn"] = blocks.init_attn_cache(
                cfg, batch, kv_len, local=(spec.mixer == "local"), dtype=dtype)
        elif spec.mixer == "mla":
            c["mla"] = mla_mod.init_mla_cache(cfg, batch, kv_len, dtype)
        elif spec.mixer == "ssd":
            c["ssd"] = ssm_mod.init_ssd_cache(cfg, batch, dtype)
        elif spec.mixer == "rglru":
            c["rglru"] = rglru_mod.init_rglru_cache(cfg, batch, dtype)
        if cfg.n_enc_layers:
            F = cfg.frontend_tokens
            c["xattn"] = {
                "k": jnp.zeros((batch, F, cfg.n_kv_heads, cfg.head_dim), dtype),
                "v": jnp.zeros((batch, F, cfg.n_kv_heads, cfg.head_dim), dtype),
            }
        return c

    cache: dict = {}
    for si, seg in enumerate(cfg.segments()):
        cache[f"seg{si}"] = {
            f"c{ci}": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (seg.repeats,) + x.shape).copy(),
                layer_cache(spec))
            for ci, spec in enumerate(seg.cycle)
        }
    return cache


def init_slot_caches(cfg: ModelConfig, n_slots: int, kv_len: int,
                     dtype=jnp.bfloat16) -> dict:
    """Per-slot decode caches for continuous batching: every leaf of the
    single-request cache (``init_cache(cfg, 1, kv_len)``) gains a leading
    slot axis. Each slot is an independent single-request cache lane —
    including the per-lane ``pos`` bookkeeping that a shared batched cache
    cannot represent when slots sit at different sequence positions."""
    single = init_cache(cfg, 1, kv_len, dtype)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_slots,) + x.shape).copy(), single)


def write_slot_cache(caches: dict, single: dict, slot) -> dict:
    """Insert a single-request cache into lane ``slot`` of a slot-stacked
    cache tree. ``slot`` may be a traced index (one compile covers all
    slots). Replaces the whole lane, so a freshly prefilled request never
    sees the previous occupant's state."""
    return jax.tree.map(
        lambda full, one: lax.dynamic_update_index_in_dim(full, one, slot, 0),
        caches, single)


def serve_unsupported_reason(cfg: ModelConfig) -> Optional[str]:
    """One precise capability reason when a config cannot be served by the
    continuous-batching engine at all, else None.  Every registered family
    is servable: decoder-only token LMs (any mixer mix), modality-frontend
    archs (requests carry their precomputed frontend embeddings), and
    encoder-decoder stacks (the encoder runs once at admission and its
    cross-attention KV is paged as a read-only static block set)."""
    return None


# serving cache group per mixer kind: "paged" layers hold per-token rows in
# shared page pools addressed by growing block tables (MLA latents are
# per-token rows too); "window" layers hold the same rows behind a sliding
# ring of blocks (freed back to the allocator once fully behind the window);
# "recurrent" layers hold O(1) per-slot scan state (no blocks at all).
_MIXER_GROUP = {"global": "paged", "mla": "paged", "local": "window",
                "ssd": "recurrent", "rglru": "recurrent"}


def serve_groups(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    """Per-layer serving-capability report: cache group -> layer indices.

    This replaces the old whole-model ``supports_paged`` boolean gate — the
    engine consumes it to build mixed layer groups (global-paged block
    tables / window block rings / recurrent state slots / static cross
    block sets) so that every arch serves under ``paged=True``.

    The mixer keys ("paged"/"window"/"recurrent") partition the layer
    list.  "cross" is an *overlay*, not part of the partition: every
    decoder layer of an enc-dec stack carries cross-attention on top of
    its self-mixer, so its indices repeat the mixer keys'.  A modality
    frontend (VLM) contributes no group of its own — its projected rows
    enter the decoder sequence and their K/V pages through the normal
    self-attention groups.  "sharable" is a second overlay: the layers
    whose paged blocks are content-addressable for cross-request prefix
    reuse — the paged layers, but only when the whole arch qualifies
    (``prefix_sharable_reason`` is None); an arch with any
    request-private group (window rings, recurrent slabs, cross sets,
    frontend rows) shares nothing."""
    out: dict[str, list[int]] = {"paged": [], "window": [], "recurrent": []}
    for li, spec in enumerate(cfg.layers()):
        out[_MIXER_GROUP[spec.mixer]].append(li)
    groups = {k: tuple(v) for k, v in out.items()}
    groups["cross"] = (tuple(range(cfg.n_layers)) if cfg.n_enc_layers
                       else ())
    whole_arch_sharable = (not cfg.n_enc_layers and not cfg.frontend
                           and not groups["window"]
                           and not groups["recurrent"])
    groups["sharable"] = groups["paged"] if whole_arch_sharable else ()
    return groups


def prefix_sharable_reason(cfg: ModelConfig) -> Optional[str]:
    """Why cross-request prefix-cache block sharing is unavailable for
    this config, or None when it is sound.

    The prefix cache's correctness condition: a cache block's physical
    content must be a pure function of the token prefix it covers.
    Causal global attention (and MLA latents) satisfy it — K/V rows at
    position i depend only on tokens <= i — but any per-request state
    breaks it, and one unsharable ingredient disqualifies the whole arch
    (there is no per-layer opt-in: a skipped prefill must be skippable
    for *every* layer or the prompt still has to be recomputed)."""
    if cfg.n_enc_layers:
        return ("enc-dec cross-attention mixes per-request encoder frames "
                "into every decoder layer, so block content is not a "
                "function of the token prefix")
    if cfg.frontend:
        return ("modality-frontend rows prepend per-request embeddings, so "
                "every self-attention block depends on the request's "
                "frontend content, not just its tokens")
    groups = serve_groups(cfg)
    if groups["window"]:
        return ("sliding-window layers keep per-request block rings whose "
                "entries are freed and recycled in place, never "
                "content-stable")
    if groups["recurrent"]:
        return ("recurrent-state layers carry per-request scan state "
                "slabs, not content-addressable blocks")
    return None


def prompt_block_hashes(prompt, block_size: int) -> tuple[str, ...]:
    """Content-addressed hash chain over a prompt's *full* cache blocks.

    Entry i commits to the entire token prefix ``prompt[:(i+1) *
    block_size]`` via ``h_i = blake2b(h_{i-1} | tokens_i)`` — so equal
    hashes mean equal prefixes and a chain lookup can stop at the first
    miss.  Only full blocks are hashed: the partial tail block is always
    private to its request.  blake2b (not Python's salted ``hash``) keeps
    the chain stable across processes, so persisted traces and multi-host
    schedulers agree on block identity."""
    toks = [int(t) for t in prompt]
    chain: list[str] = []
    parent = b""
    for i in range(len(toks) // block_size):
        block = toks[i * block_size:(i + 1) * block_size]
        payload = parent + b"|" + b",".join(b"%d" % t for t in block)
        h = hashlib.blake2b(payload, digest_size=16).hexdigest()
        chain.append(h)
        parent = h.encode()
    return tuple(chain)


def init_paged_caches(cfg: ModelConfig, n_slots: int, n_pages: int,
                      block_size: int, dtype=jnp.bfloat16) -> dict:
    """Paged decode cache tree with mixed layer groups, stacked to
    ``[repeats, ...]`` to mirror the scan segments like ``init_cache``:

    * global attention — shared ``[n_pages, block_size, KV * hd]`` K/V
      page pools, lane-dense (no slot axis — lanes are carved out by block
      tables);
    * MLA — shared latent page pools (ckv/krope rows), same block tables;
    * sliding-window attention — the same pool shape, addressed through
      window ring tables (entries behind the window are null);
    * ssd/rglru — slot-stacked O(1) recurrent state ``[repeats, n_slots,
      ...]`` (one lane per slot, no blocks);
    * enc-dec cross attention — every decoder layer additionally carries
      an ``xattn`` K/V page pool, addressed through per-slot *static*
      cross tables (written once at admission, never extended).

    The stacked pools ride whole in the carry of the segment's layer scan
    (``_run_segment``), so a jitted step that donates the tree updates
    them in place.
    """
    def stack(leaf: dict, repeats: int) -> dict:
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (repeats,) + x.shape).copy(), leaf)

    cache: dict = {}
    for si, seg in enumerate(cfg.segments()):
        seg_c: dict = {}
        for ci, spec in enumerate(seg.cycle):
            if spec.mixer in ("global", "local"):
                leaf = {"attn": blocks.init_paged_attn_cache(
                    cfg, n_pages, block_size, dtype)}
            elif spec.mixer == "mla":
                leaf = {"mla": mla_mod.init_paged_mla_cache(
                    cfg, n_pages, block_size, dtype)}
            elif spec.mixer == "ssd":
                leaf = {"ssd": ssm_mod.init_ssd_cache(cfg, n_slots, dtype)}
            else:
                assert spec.mixer == "rglru", spec.mixer
                leaf = {"rglru": rglru_mod.init_rglru_cache(cfg, n_slots,
                                                            dtype)}
            if cfg.n_enc_layers:
                leaf["xattn"] = blocks.init_paged_attn_cache(
                    cfg, n_pages, block_size, dtype)
            seg_c[f"c{ci}"] = stack(leaf, seg.repeats)
        cache[f"seg{si}"] = seg_c
    return cache


def _cache_entries(cfg: ModelConfig, caches: dict):
    """(spec, entry-dict) per scan cycle entry, in deterministic order."""
    for si, seg in enumerate(cfg.segments()):
        for ci, spec in enumerate(seg.cycle):
            yield spec, caches[f"seg{si}"][f"c{ci}"]


def _map_entries(cfg: ModelConfig, fn, *trees: dict) -> dict:
    """Rebuild the seg/cycle cache-tree structure with
    ``fn(spec, *entry_dicts)`` applied to every scan cycle entry."""
    out: dict = {}
    for si, seg in enumerate(cfg.segments()):
        out[f"seg{si}"] = {
            f"c{ci}": fn(spec, *(t[f"seg{si}"][f"c{ci}"] for t in trees))
            for ci, spec in enumerate(seg.cycle)}
    return out


def _scatter_state(full, one, slot):
    """Scatter a batch-1 state leaf into lane ``slot`` of the slot-stacked
    leaf (arrays are [repeats, n_slots, ...] / [repeats, 1, ...])."""
    return jax.tree.map(
        lambda f, u: lax.dynamic_update_slice_in_dim(f, u, slot, axis=1),
        full, one)


def paged_cache_leaves(cfg: ModelConfig, caches: dict) -> list[tuple]:
    """(group, (a_key, b_key), leaf) for every physical pool leaf, in
    deterministic order — the engine binds one ``PagedKVStore`` per leaf
    (tagged with its table group) and rebinds them after each jitted step.
    Recurrent state leaves are not listed (see ``state_cache_leaves``)."""
    out = []
    for spec, entry in _cache_entries(cfg, caches):
        if spec.mixer in ("global", "local"):
            group = "window" if spec.mixer == "local" else "global"
            out.append((group, ("k_pages", "v_pages"), entry["attn"]))
        elif spec.mixer == "mla":
            out.append(("global", ("ckv_pages", "krope_pages"), entry["mla"]))
        if "xattn" in entry:
            out.append(("cross", ("k_pages", "v_pages"), entry["xattn"]))
    return out


def state_cache_leaves(cfg: ModelConfig, caches: dict) -> list[dict]:
    """Slot-stacked recurrent state leaves ([repeats, n_slots, ...] arrays),
    in deterministic order."""
    return [entry[spec.mixer] for spec, entry in _cache_entries(cfg, caches)
            if spec.mixer in ("ssd", "rglru")]


def state_bytes_per_slot(cfg: ModelConfig, caches: dict) -> int:
    """Physical bytes one decode lane pins in recurrent state leaves."""
    total = 0
    for leaf in state_cache_leaves(cfg, caches):
        for arr in jax.tree.leaves(leaf):
            total += (arr.size // arr.shape[1]) * arr.dtype.itemsize
    return total


def lane_view(cfg: ModelConfig, caches: dict, slot) -> dict:
    """Chunk-prefill view of the paged cache tree for one lane: recurrent
    state leaves are sliced to ``slot`` (batch 1, carrying the scan state
    across the lane's prefill chunks); pool leaves pass through whole.
    ``slot`` may be traced — one compile covers all lanes."""
    def walk(spec: LayerSpec, entry: dict) -> dict:
        if spec.mixer in ("ssd", "rglru"):
            return {**entry, spec.mixer: jax.tree.map(
                lambda x: lax.dynamic_slice_in_dim(x, slot, 1, axis=1),
                entry[spec.mixer])}
        return entry

    return _map_entries(cfg, walk, caches)


def lane_merge(cfg: ModelConfig, caches: dict, updated: dict, slot) -> dict:
    """Fold a ``lane_view`` tree a forward pass updated back into the full
    slot-stacked tree: pool leaves are taken wholesale (they are shared),
    state leaves are scattered into lane ``slot``."""
    def walk(spec: LayerSpec, full: dict, upd: dict) -> dict:
        if spec.mixer in ("ssd", "rglru"):
            return {**upd, spec.mixer: _scatter_state(full[spec.mixer],
                                                      upd[spec.mixer], slot)}
        return upd

    return _map_entries(cfg, walk, caches, updated)


def snapshot_state_lanes(cfg: ModelConfig, caches: dict, slot) -> dict:
    """Copy lane ``slot``'s recurrent (ssd/rglru) state leaves out of the
    paged tree — the pre-draft snapshot of a speculative round.  Entries
    hold *only* the state (pool leaves are dropped), so a live snapshot
    pins O(1) lane state and never keeps superseded pools alive.
    ``slot`` may be traced — one compile covers all lanes."""
    def walk(spec: LayerSpec, entry: dict) -> dict:
        if spec.mixer in ("ssd", "rglru"):
            return {spec.mixer: jax.tree.map(
                lambda x: lax.dynamic_slice_in_dim(x, slot, 1, axis=1),
                entry[spec.mixer])}
        return {}

    return _map_entries(cfg, walk, caches)


def restore_state_lanes(cfg: ModelConfig, caches: dict, snapshot: dict,
                        slot) -> dict:
    """Scatter a ``snapshot_state_lanes`` capture back into lane ``slot``
    — the recurrent-state rewind after a draft pass polluted the lane or
    a verify pass advanced it beyond the accepted tokens."""
    def walk(spec: LayerSpec, full: dict, snap: dict) -> dict:
        if spec.mixer in ("ssd", "rglru"):
            return {**full, spec.mixer: _scatter_state(full[spec.mixer],
                                                       snap[spec.mixer], slot)}
        return full

    return _map_entries(cfg, walk, caches, snapshot)


def write_state_lanes(cfg: ModelConfig, caches: dict, single: dict,
                      slot) -> dict:
    """Insert a single-request cache's recurrent state leaves into lane
    ``slot`` of the paged tree; every other entry passes through untouched.
    The engine uses this with its zeroed scratch cache to reset a reused
    lane's state before chunked prefill starts carrying state into it."""
    def walk(spec: LayerSpec, full: dict, one: dict) -> dict:
        if spec.mixer in ("ssd", "rglru"):
            return {**full, spec.mixer: _scatter_state(full[spec.mixer],
                                                       one[spec.mixer], slot)}
        return full

    return _map_entries(cfg, walk, caches, single)


def freeze_state_lanes(cfg: ModelConfig, new_caches: dict, old_caches: dict,
                       active) -> dict:
    """After a batched paged decode step, restore the recurrent state slabs
    of inactive lanes (``active``: [n_slots] bool).

    The batched step runs every lane — retired lanes and lanes mid
    chunked-prefill included — and a recurrent layer's decode would absorb
    those lanes' garbage tokens into their state slabs (attention/MLA
    lanes are safe: their writes go through null table rows).  Masking the
    state update to active lanes keeps a chunk-prefilling lane's carried
    state untouched between its chunk steps."""
    def walk(spec: LayerSpec, new_e: dict, old_e: dict) -> dict:
        if spec.mixer in ("ssd", "rglru"):
            def sel(n, o):
                mask = active.reshape((1, active.shape[0]) +
                                      (1,) * (n.ndim - 2))
                return jnp.where(mask, n, o)
            return {**new_e, spec.mixer: jax.tree.map(sel, new_e[spec.mixer],
                                                      old_e[spec.mixer])}
        return new_e

    return _map_entries(cfg, walk, new_caches, old_caches)


def _scatter_rows(pages, row_tbl, cpos, rows, *, block_size: int,
                  null_block: int):
    """Write per-position rows into a page pool through one table row.

    ``pages``: [repeats, n_pages, block_size, width]; ``row_tbl``: [W] the
    lane's physical blocks; ``cpos``: [S] absolute cache positions (-1 =
    invalid); ``rows``: [repeats, S, *row], a row flattening to ``width``
    (dense K/V rows are [KV, hd]).  Rows whose position is -1 or whose
    block is not covered by the table are redirected to the null page."""
    width = row_tbl.shape[0]
    blk = jnp.clip(jnp.where(cpos >= 0, cpos // block_size, 0),
                   0, width - 1)
    ok = (cpos >= 0) & ((cpos // block_size) < width)
    phys = jnp.where(ok, row_tbl[blk], null_block)
    off = jnp.where(cpos >= 0, cpos % block_size, 0)
    return pages.at[:, phys, off].set(
        rows.reshape(rows.shape[:2] + pages.shape[3:]))


def insert_paged_prompt(cfg: ModelConfig, caches: dict, single: dict,
                        tables: dict, slot, *, block_size: int,
                        null_block: int, skip_below=0) -> dict:
    """Scatter a dense single-request prefill cache into the paged tree.

    ``single`` is the ``init_cache(cfg, 1, kv_len)`` tree a full prefill
    populated.  Per layer group: attention/MLA rows are written to the
    physical blocks named by their group's table row (``tables["global"]`` /
    ``tables["window"]``) at their absolute cache positions — rows whose
    position is -1 (bucket padding, empty slots) or whose block is not
    covered by the table (behind the window ring) are redirected to the
    null page; cross-attention K/V (enc-dec) lands in the slot's static
    cross block set (``tables["cross"]``) at positions ``0..F-1``;
    ssd/rglru state is inserted into lane ``slot``.  The pools' other
    lanes are untouched, so admission never perturbs running requests.

    ``skip_below`` (may be traced) suppresses attention/MLA writes below
    that cache position: on a prefix-cache hit the matched positions are
    already resident in shared blocks, and writing them again would
    clobber content other slots read (the table's head entries *are*
    those shared blocks).  The prefill itself still computes every
    position — only the writes are masked."""
    skip_below = jnp.asarray(skip_below, jnp.int32)

    def scatter(pages, row_tbl, cpos, rows):
        return _scatter_rows(pages, row_tbl, cpos, rows,
                             block_size=block_size, null_block=null_block)

    def walk(spec: LayerSpec, full: dict, one: dict) -> dict:
        if spec.mixer in ("global", "local"):
            row = tables["window" if spec.mixer == "local" else "global"]
            leaf, sl = full["attn"], one["attn"]
            cpos = sl["pos"][0]                # identical across repeats
            cpos = jnp.where(cpos >= skip_below, cpos, -1)
            out = {"attn": {
                "k_pages": scatter(leaf["k_pages"], row, cpos, sl["k"][:, 0]),
                "v_pages": scatter(leaf["v_pages"], row, cpos, sl["v"][:, 0]),
            }}
        elif spec.mixer == "mla":
            leaf, sl = full["mla"], one["mla"]
            cpos = sl["pos"][0]
            cpos = jnp.where(cpos >= skip_below, cpos, -1)
            out = {"mla": {
                "ckv_pages": scatter(leaf["ckv_pages"], tables["global"],
                                     cpos, sl["ckv"][:, 0]),
                "krope_pages": scatter(leaf["krope_pages"], tables["global"],
                                       cpos,
                                       mla_mod.pad_krope(cfg,
                                                         sl["krope"][:, 0])),
            }}
        else:
            # ssd/rglru: O(1) recurrent state into the lane
            out = {spec.mixer: _scatter_state(full[spec.mixer],
                                              one[spec.mixer], slot)}
        if "xattn" in full:
            leaf, sl = full["xattn"], one["xattn"]
            fpos = jnp.arange(sl["k"].shape[2], dtype=jnp.int32)
            out["xattn"] = {
                "k_pages": scatter(leaf["k_pages"], tables["cross"], fpos,
                                   sl["k"][:, 0]),
                "v_pages": scatter(leaf["v_pages"], tables["cross"], fpos,
                                   sl["v"][:, 0]),
            }
        return out

    return _map_entries(cfg, walk, caches, single)


def copy_paged_block(cfg: ModelConfig, caches: dict, src, dst) -> dict:
    """Copy one physical page ``src`` -> ``dst`` across every *global*-group
    pool leaf (attention K/V and MLA latent pools) — the physical half of a
    prefix-cache copy-on-write fork.  ``src``/``dst`` may be traced, so the
    engine jits this once.  Window, cross, and recurrent leaves pass
    through untouched (they are never content-shared)."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def copy(pool):
        page = lax.dynamic_index_in_dim(pool, src, axis=1, keepdims=False)
        return lax.dynamic_update_index_in_dim(pool, page, dst, axis=1)

    def walk(spec: LayerSpec, entry: dict) -> dict:
        if spec.mixer == "global":
            return {**entry, "attn": jax.tree.map(copy, entry["attn"])}
        if spec.mixer == "mla":
            return {**entry, "mla": jax.tree.map(copy, entry["mla"])}
        return entry

    return _map_entries(cfg, walk, caches)


def encode_cross_single(cfg: ModelConfig, params: dict, frontend_emb,
                        *, unroll: bool = False) -> dict:
    """Encode-at-admission for the chunked-prefill path: run the encoder
    once over one request's frame embeddings ([1, F, frontend_dim]) and
    project every decoder layer's cross-attention K/V.  Returns a tree
    shaped like the dense single-request cache restricted to its
    ``xattn`` leaves ({"k"/"v": [repeats, 1, F, KV, hd]}) —
    ``insert_cross_rows`` scatters it into the static cross block set.
    (The full-prefill admission path needs neither: its dense prefill
    already computes the encoder and the per-layer cross K/V.)"""
    enc_out = _encode(cfg, params, frontend_emb, remat=False,
                      unroll=unroll)
    B, F, _ = enc_out.shape

    def project(xp: dict) -> dict:
        he = rms_norm(enc_out, xp["ln"], cfg.norm_eps)
        xk = (he @ xp["wk"]).reshape(B, F, cfg.n_kv_heads, cfg.head_dim)
        xv = (he @ xp["wv"]).reshape(B, F, cfg.n_kv_heads, cfg.head_dim)
        return {"k": xk, "v": xv}

    out: dict = {}
    for si, seg in enumerate(cfg.segments()):
        out[f"seg{si}"] = {
            f"c{ci}": {"xattn": jax.vmap(project)(
                params[f"seg{si}"][f"c{ci}"]["xattn"])}
            for ci in range(len(seg.cycle))}
    return out


def insert_cross_rows(cfg: ModelConfig, caches: dict, cross_single: dict,
                      table, *, block_size: int, null_block: int) -> dict:
    """Scatter one request's projected cross-attention K/V rows
    (``encode_cross_single``) into the cross page pools through its static
    cross table; every non-cross leaf passes through untouched."""
    def walk(spec: LayerSpec, full: dict, one: dict) -> dict:
        if "xattn" not in one:
            return full
        leaf, sl = full["xattn"], one["xattn"]
        fpos = jnp.arange(sl["k"].shape[2], dtype=jnp.int32)
        return {**full, "xattn": {
            "k_pages": _scatter_rows(leaf["k_pages"], table, fpos,
                                     sl["k"][:, 0], block_size=block_size,
                                     null_block=null_block),
            "v_pages": _scatter_rows(leaf["v_pages"], table, fpos,
                                     sl["v"][:, 0], block_size=block_size,
                                     null_block=null_block),
        }}

    return _map_entries(cfg, walk, caches, cross_single)


def embed_prompt_rows(cfg: ModelConfig, params: dict, tokens,
                      frontend_emb=None):
    """Embedding rows for one request's full decoder input, exactly as
    ``forward`` would embed them: token embeddings (emb-scaled), with the
    projected frontend rows prepended for a modality-frontend arch.
    ``tokens``: [S]; ``frontend_emb``: [F, frontend_dim].  Returns
    [F + S, d_model].  The chunked-prefill path slices these precomputed
    rows into fixed-size chunks — a chunk may straddle the frontend/token
    boundary, which token ids alone cannot express."""
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.emb_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    if cfg.frontend and not cfg.n_enc_layers:
        assert frontend_emb is not None
        fe = frontend_emb.astype(h.dtype) @ params["frontend_proj"]
        h = jnp.concatenate([fe, h], axis=0)
    return h


def mask_cache_positions(cache: dict, true_len) -> dict:
    """Invalidate bucket-padding rows after a padded prefill: any cache slot
    holding a position ``>= true_len`` is marked empty (-1), so the pad
    tokens' K/V (attention) or latents (MLA) can never be attended to.
    Recurrent (ssd/rglru) state needs no masking — the forward's
    ``valid_len`` freezes it past the real prompt instead."""
    def walk(node):
        if isinstance(node, dict):
            if "pos" in node:
                pos = node["pos"]
                return {**node, "pos": jnp.where(pos >= true_len, -1, pos)}
            return {key: walk(val) for key, val in node.items()}
        return node

    return walk(cache)


# =============================================================================
# forward
# =============================================================================

class Aux(NamedTuple):
    """What a forward pass reports beside its logits and cache."""

    loss: jax.Array                     # router load-balancing loss (train)
    # [B, S, held + 1] int32, summed over the MoE layers: each token's
    # (token, slot) pairs per held expert, the last column the pairs sent
    # to experts held elsewhere; None without a dropless MoE layer
    expert_load: Optional[jax.Array] = None


def _add_load(a, b):
    return b if a is None else a if b is None else a + b


def _apply_layer(cfg: ModelConfig, spec: LayerSpec, p: dict, h, *,
                 positions, cache: Optional[dict], enc_out, impl: str,
                 n_groups: int, capacity_factor: float = 1.25,
                 moe_lossless: bool = False, unroll: bool = False,
                 paged_tables=None, window_tables=None, cross_tables=None,
                 layer=None, valid_len=None, context_lens=None,
                 shard_fn=None):
    """One layer. Returns (h, new_cache_or_None, aux loss, expert load or
    None).  ``layer`` is the layer's index into its segment's stacked page
    pools (paged mode); ``context_lens`` reaches the MLA layers only."""
    aux = jnp.zeros((), jnp.float32)
    load = None
    new_cache: dict = {}

    if spec.mixer in ("global", "local"):
        local = spec.mixer == "local"
        h, c = blocks.attn_layer(cfg, p["attn"], h, local=local,
                                 positions=positions,
                                 cache=cache.get("attn") if cache else None,
                                 impl=impl, unroll=unroll,
                                 paged_tables=(window_tables if local
                                               else paged_tables),
                                 layer=layer, valid_len=valid_len,
                                 shard_fn=shard_fn)
        if c is not None:
            new_cache["attn"] = c
    elif spec.mixer == "mla":
        h, c = mla_mod.mla_layer(cfg, p["mla"], h, positions=positions,
                                 cache=cache.get("mla") if cache else None,
                                 impl=impl, unroll=unroll,
                                 paged_tables=paged_tables, layer=layer,
                                 context_lens=context_lens,
                                 shard_fn=shard_fn)
        if c is not None:
            new_cache["mla"] = c
    elif spec.mixer == "ssd":
        h, c = ssm_mod.ssd_layer(cfg, p["ssd"], h,
                                 cache=cache.get("ssd") if cache else None,
                                 impl=impl, valid_len=valid_len)
        if c is not None:
            new_cache["ssd"] = c
    elif spec.mixer == "rglru":
        h, c = rglru_mod.rglru_layer(cfg, p["rglru"], h,
                                     cache=cache.get("rglru") if cache else None,
                                     valid_len=valid_len)
        if c is not None:
            new_cache["rglru"] = c

    if "xattn" in p:  # enc-dec cross attention
        F = cfg.frontend_tokens
        k_pos = jnp.arange(F, dtype=jnp.int32)
        xc = cache.get("xattn") if cache is not None else None
        if xc is not None and "k_pages" in xc:
            # paged: gather the static cross block set written at
            # admission (read-only — the pools pass through untouched).
            # Tail rows past F land on the null page; k_pos = -1 masks
            # them to exact zeros, so the reduction matches the dense
            # oracle's F-row cross attention bitwise.
            assert cross_tables is not None, "paged cross KV needs tables"
            kp, vp = xc["k_pages"], xc["v_pages"]
            B_l = cross_tables.shape[0]
            Lc = cross_tables.shape[1] * kp.shape[2]
            kv_row = (B_l, Lc, cfg.n_kv_heads, cfg.head_dim)
            xk = kp[layer, cross_tables].reshape(kv_row)
            xv = vp[layer, cross_tables].reshape(kv_row)
            j = jnp.arange(Lc, dtype=jnp.int32)
            k_pos = jnp.where(j < F, j, -1)
            new_cache["xattn"] = xc
        elif enc_out is not None:  # train/prefill: project encoder output
            xp = p["xattn"]
            he = rms_norm(enc_out, xp["ln"], cfg.norm_eps)
            B, Fs, _ = he.shape
            xk = (he @ xp["wk"]).reshape(B, Fs, cfg.n_kv_heads, cfg.head_dim)
            xv = (he @ xp["wv"]).reshape(B, Fs, cfg.n_kv_heads, cfg.head_dim)
            if cache is not None:
                new_cache["xattn"] = {"k": xk, "v": xv}
        else:  # decode / chunked prefill: cached cross kv
            assert xc is not None, \
                "enc-dec needs frontend_emb or a populated cross-KV cache"
            xk, xv = xc["k"], xc["v"]
            new_cache["xattn"] = {"k": xk, "v": xv}
        h, _ = blocks.attn_layer(cfg, p["xattn"], h, local=False,
                                 positions=positions,
                                 kv_override=(xk, xv, k_pos), impl=impl,
                                 unroll=unroll, shard_fn=shard_fn)

    if spec.ffn == "dense":
        h = blocks.ffn_layer(cfg, p["ffn"], h)
    elif spec.ffn == "moe":
        # serving scans hand the expert weights over as whole stacks
        h, a, load = blocks.moe_layer(cfg, p["moe"], h, n_groups=n_groups,
                                      capacity_factor=capacity_factor,
                                      lossless=moe_lossless,
                                      layer=layer if moe_lossless else None)
        aux = aux + a
    return h, (new_cache if new_cache else None), aux, load


def _pool_keys(entry: dict) -> tuple:
    """Keys of a layer's cache entry that hold page pools (attention K/V,
    MLA latents, cross K/V); recurrent state and dense caches hold none."""
    return tuple(k for k, leaf in entry.items()
                 if "k_pages" in leaf or "ckv_pages" in leaf)


def _run_segment(cfg: ModelConfig, seg: Segment, seg_p: dict, h, *,
                 positions, seg_cache, enc_out, impl: str, n_groups: int,
                 remat: bool, repeats: Optional[int] = None,
                 capacity_factor: float = 1.25,
                 moe_lossless: bool = False, unroll: bool = False,
                 paged_tables=None, window_tables=None, cross_tables=None,
                 valid_len=None, context_lens=None, shard_fn=None):
    """Scan the segment's layers — its first ``repeats`` cycle repeats
    when given (the truncated draft pass; the rest keep their cache).
    Returns (h, new segment cache or None, ``Aux`` summed over its layers).

    Page pools ride in the scan's *carry* as the whole stacked
    ``[seg.repeats, n_pages, ...]`` arrays: each layer writes its rows
    into, and gathers its pages from, its own index of the stack, so a
    step that donates the pools updates them in place instead of
    rebuilding a layer's pool per iteration.  Layer weights and every
    other cache leaf (recurrent state, dense caches) stay in xs/ys, but
    for the dropless MoE (``moe_lossless``) the routed experts' weights
    enter the body whole, like the pools, and the grouped-matmul kernel
    reads the layer's experts from the stack: an xs slice handed to the
    kernel would be copied out of the stack first."""
    run = seg.repeats if repeats is None else repeats
    names = [f"c{ci}" for ci in range(len(seg.cycle))]
    pools: dict = {c: {} for c in names}
    rest = None
    if seg_cache is not None:
        pools = {c: {k: seg_cache[c][k] for k in _pool_keys(seg_cache[c])}
                 for c in names}
        rest = {c: {k: v for k, v in seg_cache[c].items()
                    if k not in pools[c]} for c in names}
    stacks: dict = {}
    if moe_lossless:
        for c, spec in zip(names, seg.cycle):
            if spec.ffn == "moe":
                moe = seg_p[c]["moe"]
                stacks[c] = {w: moe[w] for w in blocks.EXPERT_WEIGHTS}
                seg_p = {**seg_p, c: {**seg_p[c], "moe": {
                    w: v for w, v in moe.items() if w not in stacks[c]}}}
    if run < seg.repeats:
        head = lambda t: jax.tree.map(lambda x: x[:run], t)
        seg_p, xs_rest = head(seg_p), head(rest)
    else:
        xs_rest = rest

    def body(carry, xs):
        hh, pl = carry
        ps, cs, layer = xs
        new_cs: dict = {}
        aux = jnp.zeros((), jnp.float32)
        load = None
        for c, spec in zip(names, seg.cycle):
            lc = None if cs is None else {**cs[c], **pl[c]}
            pc = ps[c]
            if c in stacks:
                pc = {**pc, "moe": {**pc["moe"], **stacks[c]}}
            hh, nc, a, ld = _apply_layer(
                cfg, spec, pc, hh, positions=positions, cache=lc,
                enc_out=enc_out, impl=impl, n_groups=n_groups,
                capacity_factor=capacity_factor, moe_lossless=moe_lossless,
                unroll=unroll, paged_tables=paged_tables,
                window_tables=window_tables, cross_tables=cross_tables,
                layer=layer, valid_len=valid_len, context_lens=context_lens,
                shard_fn=shard_fn)
            aux = aux + a
            load = _add_load(load, ld)
            if nc is not None:
                pl = {**pl, c: {k: nc[k] for k in pl[c]}}
                new_cs[c] = {k: v for k, v in nc.items() if k not in pl[c]}
        return (hh, pl), (new_cs if new_cs else None, aux, load)

    if remat:
        body = jax.checkpoint(body)
    (h, pools), (new_rest, auxs, loads) = lax.scan(
        body, (h, pools), (seg_p, xs_rest, jnp.arange(run, dtype=jnp.int32)),
        unroll=run if unroll else 1)
    aux = Aux(jnp.sum(auxs), None if loads is None else jnp.sum(loads, 0))
    if new_rest is None:
        return h, None, aux
    if run < seg.repeats:  # splice the run repeats over the untouched tail
        new_rest = jax.tree.map(
            lambda full, part: jnp.concatenate([part, full[run:]], axis=0),
            rest, new_rest)
    return h, {c: {**new_rest[c], **pools[c]} for c in names}, aux


def _encode(cfg: ModelConfig, params: dict, frontend_emb, *,
            remat: bool, unroll: bool):
    """Bidirectional encoder stack (non-causal self-attention + FFN) over
    stub frame embeddings [B, F, frontend_dim]; returns [B, F, d_model]."""
    he = frontend_emb.astype(params["enc_frontend"].dtype) \
        @ params["enc_frontend"]
    B, F = he.shape[0], he.shape[1]
    e_pos = jnp.arange(F, dtype=jnp.int32)

    def enc_body2(carry, ps):
        hh = carry
        pa = ps["attn"]
        hn = rms_norm(hh, pa["ln"], cfg.norm_eps)
        q = (hn @ pa["wq"]).reshape(B, F, cfg.n_heads, cfg.head_dim)
        k = (hn @ pa["wk"]).reshape(B, F, cfg.n_kv_heads, cfg.head_dim)
        v = (hn @ pa["wv"]).reshape(B, F, cfg.n_kv_heads, cfg.head_dim)
        q = blocks.apply_rope(q, e_pos, cfg.rope_theta)
        k = blocks.apply_rope(k, e_pos, cfg.rope_theta)
        o = blocks.attention(q, k, v, q_positions=e_pos, k_positions=e_pos,
                             causal=False, impl="chunked", unroll=unroll)
        hh = hh + o.reshape(B, F, cfg.q_dim) @ pa["wo"]
        hh = blocks.ffn_layer(cfg, ps["ffn"], hh)
        return hh, None

    enc_body2 = jax.checkpoint(enc_body2) if remat else enc_body2
    he, _ = lax.scan(enc_body2, he, params["enc"],
                     unroll=cfg.n_enc_layers if unroll else 1)
    return rms_norm(he, params["enc_final_norm"], cfg.norm_eps)


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
            positions: Optional[jax.Array] = None,
            frontend_emb: Optional[jax.Array] = None,
            input_embeds: Optional[jax.Array] = None,
            cache: Optional[dict] = None,
            mode: str = "train", impl: str = "chunked",
            n_groups: int = 1, remat: Optional[bool] = None,
            capacity_factor: float = 1.25,
            moe_lossless: Optional[bool] = None,
            paged_tables: Optional[jax.Array] = None,
            window_tables: Optional[jax.Array] = None,
            cross_tables: Optional[jax.Array] = None,
            valid_len=None, context_lens: Optional[jax.Array] = None,
            layer_cap: Optional[int] = None,
            shard_fn=None, unroll: bool = False):
    """Returns (logits, new_cache_or_None, ``Aux``).

    tokens: [B, S] (decode: [B, 1]).
    positions: [S] absolute positions (decode: scalar array). Defaults to
      arange over the model sequence (frontend tokens first for VLM).
    frontend_emb: [B, F, frontend_dim] stub embeddings (VLM/audio).
    input_embeds: [B, S, d_model] precomputed decoder input rows
      (``embed_prompt_rows``) replacing the embed lookup — the chunked
      prefill path of a frontend arch feeds chunk slices that may straddle
      the frontend/token boundary; ``tokens`` is ignored.
    paged_tables: [B, max_blocks] block tables when ``cache`` is the paged
      tree from ``init_paged_caches`` (decode: positions is then [B]
      per-lane; chunk prefill: B == 1, positions the chunk's [S] rows).
    window_tables: [B, max_blocks] window ring tables for sliding-window
      layers in the paged regime (entries behind the window are null).
    cross_tables: [B, cross_blocks] static cross-KV tables for enc-dec
      archs in the paged regime (written once at admission, read-only).
    valid_len: prefill only — tokens at positions >= valid_len are padding
      (bucketed prefill tails, final prefill chunks); attention caches
      must not let them displace real rows and recurrent state freezes
      past them.
    context_lens: [B] paged decode only — the rows each lane has resident
      (``pos + 1``), 0 for a lane that is not decoding (free, or mid
      chunked prefill).  Only MLA layers read it: those rows and no others
      are attended.  None reads ``pos + 1``, every lane decoding.
    layer_cap: run only the first ``layer_cap`` decoder layers (rounded
      *up* to whole cycle repeats within a segment, so a heterogeneous
      cycle is never split) before the shared final norm + unembed — the
      truncated-layer draft pass of self-speculative decoding.  Skipped
      segments pass their cache through untouched, so the returned cache
      tree keeps the full structure.
    """
    remat = (mode == "train") if remat is None else remat
    decode = mode == "decode"
    if moe_lossless is None:
        moe_lossless = decode  # decode groups are tiny; avoid capacity drops
    if shard_fn is None:
        shard_fn = lambda x, kind: x
    B, S = tokens.shape if input_embeds is None else input_embeds.shape[:2]

    # ---- encoder (enc-dec archs) -------------------------------------------
    # Serving reads cached cross KV instead of re-encoding: decode and
    # chunked prefill run with frontend_emb=None (encode-at-admission).
    enc_out = None
    if cfg.n_enc_layers and not decode and frontend_emb is not None:
        enc_out = _encode(cfg, params, frontend_emb, remat=remat,
                          unroll=unroll)
    if cfg.n_enc_layers and not decode and enc_out is None:
        # only the serving chunk-prefill path may run an encoder-less
        # prefill, and it always carries the paged cross tables; anything
        # else would silently cross-attend to zero-initialized K/V
        assert cross_tables is not None, \
            "enc-dec train/prefill needs frontend_emb"

    # ---- token embedding ------------------------------------------------------
    if input_embeds is not None:
        h = input_embeds
    else:
        h = jnp.take(params["embed"], tokens, axis=0)
        if cfg.emb_scale:
            h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)

        # VLM: prepend projected frontend embeddings
        if cfg.frontend and not cfg.n_enc_layers and not decode:
            assert frontend_emb is not None
            fe = frontend_emb.astype(h.dtype) @ params["frontend_proj"]
            h = jnp.concatenate([fe, h], axis=1)
            S = h.shape[1]

    if positions is None:
        positions = (jnp.arange(S, dtype=jnp.int32) if not decode
                     else jnp.zeros((), jnp.int32))
    h = shard_fn(h, "residual")

    # ---- decoder segments ---------------------------------------------------------
    aux_total = Aux(jnp.zeros((), jnp.float32))
    new_cache: dict = {}
    remaining = None if layer_cap is None else max(int(layer_cap), 1)
    for si, seg in enumerate(cfg.segments()):
        seg_cache = cache[f"seg{si}"] if cache is not None else None
        run = seg.repeats
        if remaining is not None:
            clen = len(seg.cycle)
            run = min(seg.repeats, -(-remaining // clen)) if remaining > 0 else 0
            remaining -= run * clen
            if run == 0:  # cap reached: pass the cache through untouched
                if seg_cache is not None:
                    new_cache[f"seg{si}"] = seg_cache
                continue
        h, ncs, aux = _run_segment(
            cfg, seg, params[f"seg{si}"], h, positions=positions,
            seg_cache=seg_cache, enc_out=enc_out, impl=impl,
            n_groups=n_groups, remat=remat, repeats=run,
            capacity_factor=capacity_factor,
            moe_lossless=moe_lossless, unroll=unroll,
            paged_tables=paged_tables, window_tables=window_tables,
            cross_tables=cross_tables, valid_len=valid_len,
            context_lens=context_lens, shard_fn=shard_fn)
        h = shard_fn(h, "residual")
        aux_total = Aux(aux_total.loss + aux.loss,
                        _add_load(aux_total.expert_load, aux.expert_load))
        if ncs is not None:
            new_cache[f"seg{si}"] = ncs

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    h = shard_fn(h, "pre_unembed")
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["unembed"])
    logits = h @ unembed.astype(h.dtype)
    if cfg.padded_vocab != cfg.vocab_size:  # mask pad ids (fused; CE-safe)
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype), logits)
    logits = shard_fn(logits, "logits")
    if cfg.final_logit_softcap:  # f32 tanh internally, bf16 out (stable + small)
        logits = softcap(logits.astype(jnp.float32),
                         cfg.final_logit_softcap).astype(h.dtype)
    return logits, (new_cache if new_cache else None), aux_total
