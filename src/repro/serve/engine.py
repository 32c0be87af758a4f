"""Batched serving: prefill/decode step factories, the static-batch ``Engine``
and the continuous-batching ``ContinuousEngine``.

``make_serve_step`` builds the function the decode-shape dry-run cells lower:
one new token for every sequence in the batch against a seq_len KV cache
(SSM/hybrid archs carry O(1) state instead — that is the point of the
long_500k cells).

``ContinuousEngine`` serves a live request stream: a slot scheduler admits
queued prompts into free decode lanes mid-stream (no batch boundaries), a
block allocator accounts the KV cache and reclaims it on EOS/max-tokens, and
per-step telemetry (slot occupancy, cache pressure, latency) feeds the paper
§3 scheduling assistants.  Two decode regimes (see docs/serving.md):

* dense (default) — a vmapped single-request lane over a slot-stacked cache
  tree; every lane carries its own absolute position, so emitted tokens are
  bit-identical to per-request greedy decoding.
* paged (``paged=True``) — the physical regime, for **every arch in the
  registry**: the per-layer capability report (``lm.serve_groups``)
  partitions the layers into mixed cache groups — global attention and MLA
  latents live in shared ``[n_pages, block_size, ...]`` page pools behind
  growing per-slot block tables; sliding-window layers use the same pools
  behind per-slot *window block rings* (blocks fully behind
  ``pos - window`` are freed back to the allocator and the published table
  entry becomes null); ssd/rglru layers hold O(1) per-slot recurrent state
  slabs (no blocks), with the allocator accounting those state slots
  separately; enc-dec decoder layers additionally cross-attend through a
  per-slot *static cross block set* — sized for exactly
  ``frontend_tokens`` rows, priced and allocated in full at admission,
  written once by the encode-at-admission step, never extended, freed at
  retirement.  A modality frontend (VLM) needs no group of its own: its
  projected rows prepend the decoder sequence and page through the normal
  self-attention tables.  Decode is one batched step that writes each
  lane's token through its group tables and attends via the gather-based
  paged kernel (window-masked for ring layers).  The pools are lane-dense
  (``[.., block_size, n_kv_heads * head_dim]``), ride whole in the layer
  scan's carry, and every jitted step that returns them takes them
  donated, so a step updates them in place.  For all-global archs the
  gathered view has exactly ``kv_len`` (+ frontend) rows
  (``% block_size == 0`` is enforced) and masked rows contribute exact
  zeros, so tokens are bit-identical to the oracle; window/recurrent
  archs agree with the oracle to greedy-argmax identity (the reduction
  orders differ in ulps — see docs/serving.md).

On top of either regime, ``bucket_prompts=True`` pads prefills to
power-of-two buckets (compile count bounded by the bucket count instead of
the number of distinct prompt lengths; recurrent state is frozen past the
true length via ``valid_len``), and ``prefill_chunk=N`` (paged only)
splits long prompts into N-token chunks interleaved with decode steps so
admission never stalls running lanes — recurrent layers carry their scan
state across the chunks, and a frontend arch's rows ride the chunk stream
as precomputed embeddings.
"""

from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.models import lm
from repro.models.config import ModelConfig, ShapeConfig
from repro.runtime.telemetry import ServeTelemetry, StepSpans

from . import sampling as sampling_mod
from .cache import (BlockAllocator, CacheConfig, CacheExhausted, CacheLayout,
                    PagedKVStore)
from .sampling import GREEDY, SamplingParams
from .scheduler import ActiveSlot, Request, SlotScheduler

PREFILL_BUCKET_FLOOR = 8


def _donates() -> bool:
    """Whether the jitted steps take the cache tree donated: on an
    accelerator, where the page pools fill device memory.  The host (CPU)
    backend keeps each step's input alive instead — memory there is not
    the constraint, and a caller that wraps a step may hand its input tree
    back as the step's result."""
    return jax.default_backend() != "cpu"


def _jit_donating(fn, argnum: int):
    """``jax.jit(fn)`` with the cache tree at ``argnum`` donated (see
    ``_donates``): the step's output pools alias its input pools, so no
    second copy of them is ever live.  The caller must drop every
    reference to the tree it passed in."""
    return jax.jit(fn, donate_argnums=(argnum,) if _donates() else ())


def bucket_length(n: int, cap: int, floor: int = PREFILL_BUCKET_FLOOR) -> int:
    """Smallest power-of-two bucket >= n (>= floor), clamped to cap."""
    b = max(floor, 1 << max(0, (n - 1).bit_length()))
    return min(max(b, n), cap)


def _pick_token(row: jax.Array, sample_args) -> jax.Array:
    """Next token from ``[B, vocab]`` last-position logits: the fused
    greedy argmax when ``sample_args`` is None (the historical path, and
    the ``Engine`` oracle), else the per-request sample —
    ``sample_args = (key, temperature, top_k, top_p)`` scalars for the
    B == 1 single-lane prefill paths.  The sampler selects the argmax
    **bitwise** at temperature 0, so passing sample_args never perturbs
    greedy identity."""
    if sample_args is None:
        return jnp.argmax(row, axis=-1).astype(jnp.int32)
    key, temp, topk, topp = sample_args
    return sampling_mod.sample_token(row[0], key, temp, topk, topp)[None]


def make_prefill_step(cfg: ModelConfig, impl: str = "chunked",
                      n_groups: int = 1, shard_fn=None, unroll: bool = False,
                      moe_lossless=None):
    """Both engines build this with ``moe_lossless=True``: capacity drops
    are a training-throughput trade whose victims depend on the batch
    shape, so a dropped prefill would make emitted tokens depend on bucket
    padding and chunk boundaries — breaking the engines' token-identity
    contract.  The dry-run cells keep the default (dropped) capacity —
    lossless dispatch buffers would distort the 32k-prompt memory
    analysis."""
    def prefill_step(params, cache, tokens, frontend_emb=None,
                     sample_args=None):
        logits, new_cache, _ = lm.forward(
            cfg, params, tokens, frontend_emb=frontend_emb, cache=cache,
            mode="prefill", impl=impl, n_groups=n_groups, shard_fn=shard_fn,
            moe_lossless=moe_lossless, unroll=unroll)
        next_tok = _pick_token(logits[:, -1, :cfg.vocab_size], sample_args)
        return next_tok, new_cache
    return prefill_step


def make_serve_step(cfg: ModelConfig, impl: str = "chunked",
                    n_groups: int = 1, shard_fn=None, unroll: bool = False):
    """decode_step(params, cache, tokens [B,1], pos) -> (next_tok, cache)."""
    def serve_step(params, cache, tokens, pos, sample_args=None):
        logits, new_cache, _ = lm.forward(
            cfg, params, tokens, positions=pos, cache=cache, mode="decode",
            impl=impl, n_groups=n_groups, shard_fn=shard_fn, unroll=unroll)
        next_tok = _pick_token(logits[:, -1, :cfg.vocab_size], sample_args)
        return next_tok, new_cache
    return serve_step


def make_bucketed_prefill_step(cfg: ModelConfig, impl: str = "chunked"):
    """prefill(params, cache, tokens [B, Sb], true_len, frontend_emb) ->
    (next_tok, cache).

    The prompt is right-padded to a bucket length Sb; causality makes the
    logits at ``true_len - 1`` exact, the padded rows' cache entries are
    position-invalidated so decode can never attend them, and
    ``valid_len=true_len`` freezes recurrent (ssd/rglru) state at the real
    prompt length (and keeps pad rows out of window ring slots).  One
    compile per bucket instead of one per distinct prompt length.

    A modality frontend prepends F projected rows to the decoder sequence,
    so every boundary — the logits read, the valid length, the position
    invalidation — shifts by F (the frontend rows themselves are real
    content, never padding).
    """
    F = cfg.frontend_tokens if (cfg.frontend and not cfg.n_enc_layers) else 0

    def prefill_step(params, cache, tokens, true_len, frontend_emb=None,
                     sample_args=None):
        logits, new_cache, _ = lm.forward(
            cfg, params, tokens, frontend_emb=frontend_emb, cache=cache,
            mode="prefill", impl=impl, moe_lossless=True,
            valid_len=true_len + F)
        last = lax.dynamic_index_in_dim(logits, F + true_len - 1, axis=1,
                                        keepdims=False)
        next_tok = _pick_token(last[:, :cfg.vocab_size], sample_args)
        return next_tok, lm.mask_cache_positions(new_cache, true_len + F)
    return prefill_step


def _load_of(aux, rows: jax.Array):
    """A step's expert load: ``aux.expert_load`` summed over the rows that
    are real work (``rows``: [B, S] bool), [held + 1] int32; None without
    a dropless MoE layer."""
    if aux.expert_load is None:
        return None
    return jnp.sum(jnp.where(rows[..., None], aux.expert_load, 0), (0, 1))


def make_paged_decode_step(cfg: ModelConfig, impl: str = "chunked"):
    """decode(params, caches, toks [B], pos [B], tables {group: [B, W]},
    active [B] bool) -> (next_toks [B], caches, load). One batched step
    over every lane; each lane writes its token's rows through its group
    tables into the shared pools.  ``load`` ([held + 1] int32, None
    without MoE layers) counts the active lanes' (token, slot) pairs per
    held expert over the MoE layers, the last entry those routed to
    experts held elsewhere.  ``active`` masks the recurrent state update to the
    lanes actually decoding — inactive lanes (retired, or mid chunked
    prefill with carried state) must not absorb their garbage tokens.
    ``sample_args = (base_keys [B,2], temperature [B], top_k [B],
    top_p [B])`` turns the fused argmax into the per-lane sampler (the
    token decided this step sits at ``pos + 1``, which derives its key);
    greedy lanes (temperature 0) still take the argmax bitwise."""
    def decode_step(params, caches, toks, pos, tables, active,
                    sample_args=None):
        logits, new_cache, aux = lm.forward(
            cfg, params, toks[:, None], positions=pos, cache=caches,
            mode="decode", impl=impl, paged_tables=tables.get("global"),
            window_tables=tables.get("window"),
            cross_tables=tables.get("cross"))
        new_cache = lm.freeze_state_lanes(cfg, new_cache, caches, active)
        row = logits[:, -1, :cfg.vocab_size]
        if sample_args is None:
            next_tok = jnp.argmax(row, axis=-1).astype(jnp.int32)
        else:
            keys, temp, topk, topp = sample_args
            tkeys = jax.vmap(lambda k, p: sampling_mod.token_key(k, p))(
                keys, pos + 1)
            next_tok = sampling_mod.sample_lanes(row, tkeys, temp, topk, topp)
        return next_tok, new_cache, _load_of(aux, active[:, None])
    return decode_step


def make_chunk_prefill_step(cfg: ModelConfig, chunk: int,
                            impl: str = "chunked", embeds: bool = False):
    """chunk(params, caches, piece, start, rows {group: [W]}, last_idx,
    slot, valid) -> (candidate_tok [1], caches, load).

    Processes one C-token slice of a prompt directly against the paged
    tree: writes the slice's rows through the lane's group tables (global
    blocks, window ring), threads the lane's recurrent state slab through
    the slice (``lane_view``/``lane_merge`` — the chunk-carried prefill
    state), attends causally over everything resident so far (enc-dec
    archs additionally cross-attend to the lane's static cross block set,
    written at admission), and returns the greedy token read at
    ``last_idx`` (only meaningful on the final slice).  ``valid`` counts
    the slice's real rows: pad rows of a final chunk freeze the recurrent
    state and are redirected to the null page.  Fixed C means exactly one
    compile regardless of prompt lengths.  ``load`` is the decode step's
    expert load over the slice's ``valid`` rows.

    ``embeds=True`` (modality-frontend archs): ``piece`` is a [1, C,
    d_model] slice of the precomputed decoder input rows
    (``lm.embed_prompt_rows``) instead of [1, C] token ids — a chunk can
    then straddle the frontend/token boundary.
    """
    def chunk_step(params, caches, piece, start, rows, last_idx, slot,
                   valid, sample_args=None):
        positions = start + jnp.arange(chunk, dtype=jnp.int32)
        g_row = rows.get("global")
        w_row = rows.get("window")
        x_row = rows.get("cross")
        sub = lm.lane_view(cfg, caches, slot)
        logits, new_sub, aux = lm.forward(
            cfg, params, tokens=None if embeds else piece,
            input_embeds=piece if embeds else None,
            positions=positions, cache=sub,
            mode="prefill", impl=impl,
            paged_tables=None if g_row is None else g_row[None],
            window_tables=None if w_row is None else w_row[None],
            cross_tables=None if x_row is None else x_row[None],
            moe_lossless=True, valid_len=valid)
        caches = lm.lane_merge(cfg, caches, new_sub, slot)
        last = lax.dynamic_index_in_dim(logits, last_idx, axis=1,
                                        keepdims=False)
        tok = _pick_token(last[:, :cfg.vocab_size], sample_args)
        rows = jnp.arange(chunk)[None] < valid
        return tok, caches, _load_of(aux, rows)
    return chunk_step


def make_draft_decode_step(cfg: ModelConfig, draft_layers: int,
                           impl: str = "chunked"):
    """draft(params, caches, tok, pos, rows {group: [W]}, slot, key, temp,
    topk, topp) -> (next_tok, draft_probs [vocab], caches).

    One truncated-layer (``layer_cap=draft_layers``) decode step for a
    single lane — the self-speculative draft pass.  The draft token's K/V
    rows land through the lane's group tables exactly where the verify
    pass will rewrite them (a rejected row sits beyond the lane's rewound
    position, so the attention mask never reads it before the next
    accepted token overwrites it); the lane's recurrent state advances and
    is snapshot/restored by the engine around the whole draft window.
    Returns the post-filter draft distribution — the ``q`` of the
    rejection-sampling acceptance rule."""
    def draft_step(params, caches, tok, pos, rows, slot, key, temp, topk,
                   topp):
        g_row = rows.get("global")
        w_row = rows.get("window")
        x_row = rows.get("cross")
        sub = lm.lane_view(cfg, caches, slot)
        logits, new_sub, _ = lm.forward(
            cfg, params, tok.reshape(1, 1), positions=pos.reshape(1),
            cache=sub, mode="decode", impl=impl,
            paged_tables=None if g_row is None else g_row[None],
            window_tables=None if w_row is None else w_row[None],
            cross_tables=None if x_row is None else x_row[None],
            layer_cap=draft_layers)
        caches = lm.lane_merge(cfg, caches, new_sub, slot)
        row = logits[0, -1, :cfg.vocab_size]
        nxt = sampling_mod.sample_token(row, key, temp, topk, topp)
        return nxt, sampling_mod.sampling_probs(row, temp, topk, topp), caches
    return draft_step


def make_verify_step(cfg: ModelConfig, width: int, impl: str = "chunked"):
    """verify(params, caches, toks [width], start, rows, slot, valid) ->
    (logits [width, vocab], caches).

    One chunk-shaped full-model pass over ``[x_t, d_1..d_k]`` (padded to
    the static ``width = speculate + 1``) against the paged tree — the
    verification step of self-speculative decoding: all k drafts are
    scored in a single batched step through the existing paged kernel
    path.  Row ``i``'s logits are the full model's distribution for draft
    slot ``i`` (row ``k`` the bonus token).  ``valid = k + 1`` masks the
    pad tail: recurrent state freezes past it and pad-row K/V writes land
    beyond the lane's position, where the per-query causal mask
    (``j <= q_position``) keeps them invisible until overwritten."""
    use_embeds = bool(cfg.frontend and not cfg.n_enc_layers)

    def verify_step(params, caches, toks, start, rows, slot, valid):
        positions = start + jnp.arange(width, dtype=jnp.int32)
        g_row = rows.get("global")
        w_row = rows.get("window")
        x_row = rows.get("cross")
        sub = lm.lane_view(cfg, caches, slot)
        embeds = None
        tokens = toks[None]
        if use_embeds:
            # a VLM's prefill path embeds explicitly (its frontend rows
            # are long resident by decode time — verify rows are plain
            # tokens, embedded exactly as forward's own token branch)
            h = jnp.take(params["embed"], toks, axis=0)
            if cfg.emb_scale:
                h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
            embeds, tokens = h[None], None
        logits, new_sub, _ = lm.forward(
            cfg, params, tokens, input_embeds=embeds, positions=positions,
            cache=sub, mode="prefill", impl=impl,
            paged_tables=None if g_row is None else g_row[None],
            window_tables=None if w_row is None else w_row[None],
            cross_tables=None if x_row is None else x_row[None],
            moe_lossless=True, valid_len=valid)
        caches = lm.lane_merge(cfg, caches, new_sub, slot)
        return logits[0, :, :cfg.vocab_size], caches
    return verify_step


@dataclass
class Engine:
    """Minimal batched greedy-decoding engine (examples + tests)."""

    cfg: ModelConfig
    params: dict
    kv_len: int
    dtype: object = jnp.float32
    impl: str = "chunked"

    def __post_init__(self):
        self._prefill = jax.jit(make_prefill_step(self.cfg, self.impl,
                                                  moe_lossless=True))
        self._decode = jax.jit(make_serve_step(self.cfg, self.impl))

    def generate(self, prompts: jax.Array, max_new_tokens: int,
                 frontend_emb: Optional[jax.Array] = None) -> jax.Array:
        B, S = prompts.shape
        F = (self.cfg.frontend_tokens
             if (self.cfg.frontend and not self.cfg.n_enc_layers) else 0)
        cache = lm.init_cache(self.cfg, B, self.kv_len + F, self.dtype)
        tok, cache = self._prefill(self.params, cache, prompts, frontend_emb)
        out = [tok]
        pos = S + F
        for t in range(max_new_tokens - 1):
            tok, cache = self._decode(self.params, cache, tok[:, None],
                                      jnp.asarray(pos + t, jnp.int32))
            out.append(tok)
        return jnp.stack(out, axis=1)


@dataclass
class ContinuousEngine:
    """Continuous-batching greedy-decoding engine (every registry arch).

    Requests are ``submit()``-ed with an arrival step (VLM / enc-dec
    requests carry their precomputed frontend embeddings), then ``run()``
    drives the loop: admit arrived requests into free slots, prefill them
    (whole, bucketed, or in interleaved chunks; the encoder / frontend
    projection runs once at admission), one decode step across all lanes
    with per-slot positions, retire slots on EOS/max-tokens and reclaim
    their cache blocks.  A lane's computation is exactly the B=1 decode
    path, so outputs are token-identical to ``Engine.generate`` per request
    in every mode.

    Modes (see module docstring and docs/serving.md):

    * ``paged=True`` — physical paged cache with mixed layer groups built
      from the per-layer capability report (``lm.serve_groups``): shared
      page pools + growing per-slot block tables for global attention and
      MLA latents, window block rings for sliding-window layers, O(1)
      per-slot state slabs for ssd/rglru layers, static per-slot cross
      block sets for enc-dec cross-attention KV (allocated whole at
      admission, never extended).  Attention groups require
      ``(kv_len + frontend rows) % block_size == 0``.
    * ``bucket_prompts=True`` — pad prefills to power-of-two buckets; the
      prefill compile count is bounded by the bucket count.
    * ``prefill_chunk=N`` — (paged only) split prompts into N-token chunks,
      one chunk per engine step, interleaved with decode of running lanes;
      exactly one prefill compile regardless of prompt lengths.  Recurrent
      layers carry their scan state across a lane's chunks; a frontend
      arch's projected rows ride the chunk stream as embedding rows.
    * ``prefix_cache=True`` — (paged only, archs where
      ``lm.prefix_sharable_reason`` is None) content-addressed block
      reuse: admissions match their prompt hash chain against committed
      blocks and share the hits read-only (CoW on the one divergent
      write).  With chunked prefill the skipped prefix is skipped in
      *compute* too (chunks start at the first uncached position);
      whole-prompt prefills recompute but share the memory.

    Admission pricing (``pricing=``, see ``SlotScheduler``): ``"worst"``
    (default) reserves each request's full ``prompt + max_new`` growth at
    admission so decode can never exhaust the pool; ``"lazy"`` reproduces
    the historical oversubscription, backstopped by preempt-and-requeue —
    on a mid-decode ``CacheExhausted`` the engine evicts the *youngest*
    slot, requeues its request at the queue head, and retries; strict
    FCFS plus greedy determinism keeps every request's tokens identical.
    ``cache_blocks`` overrides the self-sized block pool (the way to an
    oversubscribed pool; the default sizes for every lane's worst case).
    """

    cfg: ModelConfig
    params: dict
    kv_len: int = 0
    n_slots: Optional[int] = None
    dtype: object = jnp.float32
    impl: str = "chunked"
    block_size: int = 16
    paged: bool = False
    bucket_prompts: bool = False
    prefill_chunk: int = 0
    prefix_cache: bool = False
    pricing: str = "worst"
    cache_blocks: Optional[int] = None
    # self-speculative decoding (paged only): draft up to ``speculate``
    # tokens per lane per step with a truncated-layer pass
    # (``draft_layers``, default half the stack rounded up to whole scan
    # cycles), verify them in one chunk-shaped step through the paged
    # kernel, accept by rejection sampling (token-identical to the oracle
    # under greedy), and rewind the paged cache past the accepted window
    speculate: int = 0
    draft_layers: Optional[int] = None
    telemetry: Optional[ServeTelemetry] = None
    # optional compiled-plan artifact (repro.core.plan.CompiledPlan): sizes
    # the cache length and lane count from the planned decode shape instead
    # of re-deriving them, and gives --adapt the plan it should rebalance
    plan: Optional[object] = field(default=None, repr=False)
    _next_rid: int = field(default=0, repr=False)

    def __post_init__(self):
        reason = lm.serve_unsupported_reason(self.cfg)
        if reason is not None:
            raise NotImplementedError(f"{self.cfg.name}: {reason}")
        if self.plan is not None:
            # full-config equality, not name equality: cfg.reduced() keeps
            # the name, and a plan for the full model must not size (or
            # later adapt) an engine serving the reduced one
            if self.plan.cfg != self.cfg:
                raise ValueError(
                    f"plan was compiled for {self.plan.cfg.name!r} "
                    f"(dims differ or different arch), engine serves "
                    f"{self.cfg.name!r}")
            pshape = self.plan.shape
            # explicit sizing must AGREE with the plan, never contradict
            # it: the attached plan is what --adapt rebalances, so a
            # mismatch would adapt the wrong placement problem
            if self.kv_len > 0 and self.kv_len != int(pshape.seq_len):
                raise ValueError(
                    f"plan models seq_len={pshape.seq_len} but "
                    f"kv_len={self.kv_len} was passed; drop kv_len= or "
                    "compile the plan for the served decode shape")
            if (self.n_slots is not None
                    and self.n_slots != int(pshape.global_batch)):
                raise ValueError(
                    f"plan models global_batch={pshape.global_batch} but "
                    f"n_slots={self.n_slots} was passed; drop n_slots= or "
                    "compile the plan for the served decode shape")
            self.kv_len = int(pshape.seq_len)
            self.n_slots = int(pshape.global_batch)
        if self.n_slots is None:
            self.n_slots = 4
        if self.kv_len <= 0:
            raise ValueError("kv_len must be positive (set it directly or "
                             "pass a CompiledPlan via plan=)")
        if self.prefill_chunk and not self.paged:
            raise ValueError("prefill_chunk requires paged=True (chunks are "
                             "written straight into the page pools)")
        if self.prefix_cache:
            if not self.paged:
                raise ValueError("prefix_cache requires paged=True (block "
                                 "reuse shares physical pages)")
            reason = lm.prefix_sharable_reason(self.cfg)
            if reason is not None:
                raise ValueError(f"{self.cfg.name}: prefix cache "
                                 f"unavailable — {reason}")
        if self.speculate < 0:
            raise ValueError("speculate must be >= 0")
        if self.speculate and not self.paged:
            raise ValueError("speculate requires paged=True (the rewind "
                             "path truncates block tables and window rings)")
        if self.draft_layers is None:
            self.draft_layers = max(1, self.cfg.n_layers // 2)
        elif self.draft_layers < 1:
            raise ValueError("draft_layers must be >= 1")
        groups = lm.serve_groups(self.cfg)
        self._has_global = bool(groups["paged"])
        self._has_window = bool(groups["window"])
        self._has_state = bool(groups["recurrent"])
        self._has_cross = bool(groups["cross"])
        # a VLM frontend's projected rows share the decoder's self-attention
        # cache: every lane physically holds F extra rows ahead of its
        # prompt (enc-dec frames live in the separate cross block set
        # instead, so they add nothing here)
        self._frontend_extra = (self.cfg.frontend_tokens
                                if (self.cfg.frontend and
                                    not self.cfg.n_enc_layers) else 0)
        self._kv_total = self.kv_len + self._frontend_extra
        has_blocks = self._has_global or self._has_window
        if self.paged and has_blocks and self._kv_total % self.block_size:
            raise ValueError(
                f"paged mode needs kv_len + frontend rows ({self._kv_total}) "
                f"divisible by block_size ({self.block_size}) so the "
                "gathered KV view matches the dense oracle shape (token "
                "identity)")
        if self.paged:
            # per-slot block budget by group: global tables grow to the
            # full context; a window ring is capped at O(window) blocks;
            # an enc-dec cross block set is a fixed blocks_for(F) price
            per_slot = (self._kv_total // self.block_size
                        if self._has_global else 0)
            per_slot += self._window_cap_blocks()
            per_slot += self._cross_cap_blocks()
            n_blocks = self.n_slots * per_slot
        else:
            # dense accounting must budget *physical* rows — kv_len plus a
            # VLM's frontend_extra — or worst-case growth of a full-kv_len
            # request would exhaust the pool mid-decode (the old
            # self.kv_len sizing did exactly that for frontend archs)
            n_blocks = self.n_slots * -(-self._kv_total // self.block_size)
        if self.cache_blocks is not None:
            # explicit (usually oversubscribed) pool: worst pricing then
            # throttles admission to what truly fits, lazy pricing leans
            # on preempt-and-requeue
            if self.cache_blocks < 1:
                raise ValueError("cache_blocks must be >= 1")
            n_blocks = self.cache_blocks
        self.allocator = BlockAllocator(CacheConfig(
            block_size=self.block_size, n_blocks=n_blocks))
        self.scheduler = SlotScheduler(self.n_slots, self.allocator,
                                       self.kv_len, pricing=self.pricing)
        if self.telemetry is None:
            self.telemetry = ServeTelemetry()

        self._prefill = jax.jit(make_prefill_step(self.cfg, self.impl,
                                                  moe_lossless=True))
        self._prefill_b = jax.jit(make_bucketed_prefill_step(self.cfg,
                                                             self.impl))
        self._toks = jnp.zeros((self.n_slots,), jnp.int32)
        self._pos = jnp.zeros((self.n_slots,), jnp.int32)
        # per-lane sampling state, refreshed at admission: base PRNG keys
        # plus the vectorized (temperature, top_k, top_p) lanes the decode
        # steps sample with (greedy defaults keep the argmax bitwise)
        self._skeys = jnp.zeros((self.n_slots, 2), jnp.uint32)
        self._temp = jnp.zeros((self.n_slots,), jnp.float32)
        self._topk = jnp.zeros((self.n_slots,), jnp.int32)
        self._topp = jnp.ones((self.n_slots,), jnp.float32)
        self._samp: dict[int, SamplingParams] = {}
        self._skey_host: dict[int, jax.Array] = {}
        self._now = 0
        self._rids: set = set()
        # slot -> [prompt tokens/rows, chunks done, skip] while
        # chunk-prefilling (``skip`` = prefix-cache positions not recomputed)
        self._prefilling: dict[int, list] = {}
        # (preemptions, hit_tokens, lookup_tokens) at the last recorded
        # step — _record_step reports per-step deltas of these ledgers
        self._stats_last = (0, 0, 0)
        # the step in progress (``run()`` makes one per step)
        self._spans = StepSpans(0)
        # expert loads of programs dispatched and not yet brought to the
        # host, and the sum of those the step in progress brought
        self._loads: list = []
        self._step_load = None

        if self.paged:
            self._init_paged()
        else:
            serve_step = make_serve_step(self.cfg, self.impl)

            def lane_decode(params, cache, tok, pos, key, temp, topk, topp):
                # the token decided this step sits at pos + 1 — that
                # position derives its per-request key
                tkey = sampling_mod.token_key(key, pos + 1)
                nt, nc = serve_step(params, cache, tok.reshape(1, 1), pos,
                                    (tkey, temp, topk, topp))
                return nt[0], nc

            self._decode = jax.jit(jax.vmap(
                lane_decode, in_axes=(None, 0, 0, 0, 0, 0, 0, 0)))

            # one fused dispatch per admission: lane insert + token/pos scatter
            def admit_update(caches, single, toks, pos, slot, tok, start_pos):
                caches = lm.write_slot_cache(caches, single, slot)
                return (caches, toks.at[slot].set(tok),
                        pos.at[slot].set(start_pos))

            self._insert = jax.jit(admit_update)
            self._caches = lm.init_slot_caches(self.cfg, self.n_slots,
                                               self._kv_total, self.dtype)
        # fixed by the pools' shapes and the layout: reckoned once
        self._capacity_bytes = self.allocator.capacity_bytes()

    @staticmethod
    def decode_shape_for(kv_len: int, n_slots: int) -> ShapeConfig:
        """The planning shape for a serving configuration — the single
        constructor every call site (launcher, benchmarks, the engine
        itself) must share so compiled plans key identically."""
        return ShapeConfig(f"serve_decode_{kv_len}", kv_len, n_slots,
                           "decode")

    def decode_shape(self) -> ShapeConfig:
        """The decode traffic this engine actually serves — max sequence
        length (cache capacity) x lane count.  This is the shape adaptation
        should plan for (``launch/serve.py --adapt`` compiles against it
        instead of a hardcoded registry shape)."""
        return self.decode_shape_for(self.kv_len, self.n_slots)

    def _window_cap_blocks(self) -> int:
        """Most blocks one lane's window ring can pin simultaneously:
        blocks covering the window span plus block-alignment slack, plus
        the in-flight slice during chunked prefill — never more than a
        full-context table."""
        if not self._has_window:
            return 0
        bf = lambda n: -(-n // self.block_size)
        wc = min(self._kv_total, self.cfg.window_size)
        cap = bf(wc) + 1 + (bf(self.prefill_chunk) if self.prefill_chunk
                            else 0)
        return min(bf(self._kv_total), cap)

    def _cross_cap_blocks(self) -> int:
        """Static per-slot cross block set size: blocks covering the
        encoder's ``frontend_tokens`` rows (0 for non-enc-dec archs)."""
        if not self._has_cross:
            return 0
        return -(-self.cfg.frontend_tokens // self.block_size)

    def _init_paged(self) -> None:
        """Physical regime: page pools, per-group block tables, recurrent
        state slabs, static cross block sets, store bindings."""
        cache_cfg = self.allocator.config
        null = cache_cfg.null_block
        self._max_blocks = self._kv_total // self.block_size
        self._cross_width = self._cross_cap_blocks()
        self._caches = lm.init_paged_caches(
            self.cfg, self.n_slots, cache_cfg.n_blocks + 1, self.block_size,
            self.dtype)
        # one PagedKVStore per pool leaf, tagged with its table group — the
        # allocator owns the physical pools between steps (per-group
        # residency telemetry, gather_slot)
        for group, keys, leaf in lm.paged_cache_leaves(self.cfg,
                                                       self._caches):
            self.allocator.attach_store(PagedKVStore.from_pools(
                cache_cfg, leaf[keys[0]], leaf[keys[1]]), group=group)
        self.allocator.set_layout(CacheLayout(
            has_global=self._has_global,
            window=min(self._kv_total, self.cfg.window_size)
            if self._has_window else 0,
            window_cap_blocks=self._window_cap_blocks(),
            state_slots=self.n_slots if self._has_state else 0,
            state_bytes_per_slot=lm.state_bytes_per_slot(self.cfg,
                                                         self._caches)
            if self._has_state else 0,
            prefill_chunk=self.prefill_chunk,
            cross_tokens=self.cfg.frontend_tokens if self._has_cross else 0,
            cross_cap_blocks=self._cross_width,
            frontend_extra=self._frontend_extra,
            sharable=self.prefix_cache))
        self._null_row = jnp.full((self._max_blocks,), null, jnp.int32)
        self._null_rows = {"global": self._null_row,
                           "window": self._null_row,
                           "cross": jnp.full((self._cross_width,), null,
                                             jnp.int32)}
        # one published [n_slots, width] table per block group
        self._tables: dict[str, jax.Array] = {}
        if self._has_global:
            self._tables["global"] = jnp.tile(self._null_row[None],
                                              (self.n_slots, 1))
        if self._has_window:
            self._tables["window"] = jnp.tile(self._null_row[None],
                                              (self.n_slots, 1))
        if self._has_cross:
            self._tables["cross"] = jnp.tile(self._null_rows["cross"][None],
                                             (self.n_slots, 1))
        self._rows: dict[int, dict[str, jax.Array]] = {}
        self._host_pos: dict[int, int] = {}

        # every jitted function that returns the pool tree takes it donated
        # (``_jit_donating``); the ``_caches`` setter rebinds the stores to
        # the returned pools, so nothing keeps a donated one
        self._decode_prog = _jit_donating(
            make_paged_decode_step(self.cfg, self.impl), 1)
        if self.prefill_chunk:
            self._chunk = _jit_donating(make_chunk_prefill_step(
                self.cfg, self.prefill_chunk, self.impl,
                embeds=bool(self._frontend_extra)), 1)

        def paged_insert(caches, single, rows, slot, skip):
            return lm.insert_paged_prompt(
                self.cfg, caches, single, rows, slot,
                block_size=self.block_size, null_block=null,
                skip_below=skip)

        if self.prefix_cache:
            # physical page copy for copy-on-write forks: the allocator
            # hands out (src, dst) block ids, this moves the bytes
            def copy_block(caches, src, dst):
                return lm.copy_paged_block(self.cfg, caches, src, dst)

            self._copy_block = _jit_donating(copy_block, 0)

        def reset_state(caches, single, slot):
            return lm.write_state_lanes(self.cfg, caches, single, slot)

        self._reset_state = _jit_donating(reset_state, 0)

        if self.speculate:
            self._draft_step = _jit_donating(make_draft_decode_step(
                self.cfg, self.draft_layers, self.impl), 1)
            self._verify_step = _jit_donating(make_verify_step(
                self.cfg, self.speculate + 1, self.impl), 1)
            self._accept = jax.jit(sampling_mod.speculative_accept)
            if self._has_state:
                def snapshot(caches, slot):
                    return lm.snapshot_state_lanes(self.cfg, caches, slot)

                def restore(caches, snap, slot):
                    return lm.restore_state_lanes(self.cfg, caches, snap,
                                                  slot)

                self._snapshot = jax.jit(snapshot)
                self._restore = _jit_donating(restore, 0)

        if self._has_cross:
            # encode-at-admission for the chunked path: the encoder runs
            # once per request and its projected cross K/V is scattered
            # into the slot's static cross block set (the full-prefill
            # path computes both inside the dense prefill instead)
            def encode_cross(params, fe):
                return lm.encode_cross_single(self.cfg, params, fe)

            def insert_cross(caches, cross_single, row):
                return lm.insert_cross_rows(
                    self.cfg, caches, cross_single, row,
                    block_size=self.block_size, null_block=null)

            self._encode_cross = jax.jit(encode_cross)
            self._insert_cross = _jit_donating(insert_cross, 0)

        def lane_set(toks, pos, tables, slot, tok, start_pos, rows):
            tables = {g: tables[g].at[slot].set(rows[g]) for g in tables}
            return (toks.at[slot].set(tok), pos.at[slot].set(start_pos),
                    tables)

        self._insert_p = _jit_donating(paged_insert, 0)
        self._lane_set = jax.jit(lane_set)

    def _decode_p(self, params, caches, toks, pos, tables, active,
                  sample_args=None) -> tuple:
        """The paged decode program: (next_toks, caches); its expert load
        waits for the step's sync (``_sync``)."""
        toks, caches, load = self._decode_prog(params, caches, toks, pos,
                                               tables, active, sample_args)
        if load is not None:
            self._loads.append(load)
        return toks, caches

    def _sync(self, toks) -> np.ndarray:
        """Bring ``toks`` to the host, and with them, in the same transfer
        call, every expert load waiting; their sum joins the step's."""
        host, loads = jax.device_get((toks, self._loads))
        self._loads = []
        for load in loads:
            self._step_load = (load if self._step_load is None
                               else self._step_load + load)
        return np.asarray(host)

    @functools.cached_property
    def _fresh(self) -> dict:
        """Reusable zeroed single-request cache fed to every full prefill
        and to the recurrent-state reset (jax arrays are immutable, so
        sharing the template across admissions is safe and saves an
        alloc+zero per request).  Built on first use: chunked prefill of an
        attention-only arch never needs it, and at full width it is a whole
        dense ``kv_len`` cache of device memory."""
        return lm.init_cache(self.cfg, 1, self._kv_total, self.dtype)

    @property
    def _caches(self) -> dict:
        return self._cache_tree

    @_caches.setter
    def _caches(self, tree: dict) -> None:
        # a jitted step consumes the pools it was given (donated) and
        # returns them updated: the allocator's stores must follow at once,
        # or they hold deleted arrays (or, undonated, pin a second copy)
        self._cache_tree = tree
        self._rebind_stores()

    def _rebind_stores(self) -> None:
        """Hand the current pool arrays to the allocator's stores."""
        if not self.allocator.stores:
            return
        for (_, keys, leaf), store in zip(
                lm.paged_cache_leaves(self.cfg, self._caches),
                self.allocator.stores):
            store.rebind(leaf[keys[0]], leaf[keys[1]])

    # -- disaggregated prefill/decode block handoff ------------------------------
    def export_prefix_blocks(self, block_hashes) -> list[tuple]:
        """Read the physical content of the committed blocks backing the
        longest resident prefix of ``block_hashes`` — the *export* side of
        a prefill -> decode handoff (``serve.cache.BlockTransferBuffer``).

        Each entry is ``(hash, payload)`` where the payload is one
        ``(k_page, v_page)`` pair per global-group pool leaf, in the
        engine's deterministic leaf order (identical across replicas of
        the same config, so payloads import positionally).  Reading
        copies nothing out of the allocator's books: the blocks stay
        owned (cached or live) by this replica's pool."""
        if not self.prefix_cache:
            raise ValueError("export_prefix_blocks requires prefix_cache "
                             "(the handoff is keyed by the content index)")
        gstores = [s for s, g in zip(self.allocator.stores,
                                     self.allocator.store_groups)
                   if g == "global"]
        out: list[tuple] = []
        for h in block_hashes or ():
            block = self.allocator.lookup_block(h)
            if block is None:
                break
            out.append((h, tuple((s.k_pages[:, block], s.v_pages[:, block])
                                 for s in gstores)))
        return out

    def import_prefix_blocks(self, entries) -> int:
        """Install exported ``(hash, payload)`` chain entries into this
        replica's pool as refcount-0 *cached* committed blocks — the
        *import* side of the handoff.  After this, admitting a request
        whose hash chain is covered is an ordinary full prefix-cache hit:
        chunked prefill recomputes only the unhashed tail (plus the
        mandatory last prompt position, CoW-forked as usual), and decode
        proceeds token-identically.  Returns the number of blocks whose
        content was physically installed; hashes already resident are
        skipped, and a pool too full to take the whole chain takes a
        prefix (graceful degradation — the rest is recomputed)."""
        if not self.prefix_cache:
            raise ValueError("import_prefix_blocks requires prefix_cache")
        pairs = self.allocator.inject_cached([h for h, _ in entries])
        if not pairs:
            return 0
        by_hash = dict(entries)
        leaves = [(keys, leaf) for group, keys, leaf in
                  lm.paged_cache_leaves(self.cfg, self._caches)
                  if group == "global"]
        # one scatter per pool: each update rewrites the whole pool
        blocks = jnp.asarray([block for _, block in pairs], jnp.int32)
        for i, (keys, leaf) in enumerate(leaves):
            for j, key in enumerate(keys):
                pages = jnp.stack([by_hash[h][i][j] for h, _ in pairs], axis=1)
                leaf[key] = leaf[key].at[:, blocks].set(pages)
        self._rebind_stores()
        return len(pairs)

    @property
    def now(self) -> int:
        """Current engine step — submit() arrivals are absolute against it."""
        return self._now

    # -- intake -----------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, *, rid=None,
               arrival: int = 0, eos_id: Optional[int] = None,
               frontend_emb=None,
               sampling: Optional[SamplingParams] = None) -> object:
        """Queue a request; returns its id. ``prompt`` is a 1-D token id
        sequence; ``arrival`` is the engine step at which it becomes
        admissible (0 = immediately).  VLM / enc-dec configs require
        ``frontend_emb`` — the request's precomputed stub embeddings of
        shape [frontend_tokens, frontend_dim] (encoded / projected once at
        admission).  ``sampling`` carries the request's per-lane sampling
        configuration (temperature / top-k / top-p / seed); None is exact
        greedy, bitwise identical to the pre-sampling engine."""
        prompt = [int(t) for t in prompt]
        if sampling is not None and not isinstance(sampling, SamplingParams):
            raise ValueError(
                f"sampling must be a SamplingParams, got {type(sampling)}")
        needs_fe = bool(self.cfg.frontend or self.cfg.n_enc_layers)
        if needs_fe:
            if frontend_emb is None:
                raise ValueError(
                    f"{self.cfg.name}: requests must carry frontend_emb "
                    f"[{self.cfg.frontend_tokens}, {self.cfg.frontend_dim}] "
                    "(precomputed modality-frontend embeddings)")
            frontend_emb = jnp.asarray(frontend_emb)
            want = (self.cfg.frontend_tokens, self.cfg.frontend_dim)
            if frontend_emb.shape != want:
                raise ValueError(
                    f"{self.cfg.name}: frontend_emb shape "
                    f"{frontend_emb.shape} != {want}")
        elif frontend_emb is not None:
            raise ValueError(f"{self.cfg.name} is a decoder-only token LM; "
                             "it takes no frontend_emb")
        if rid is None:
            while self._next_rid in self._rids:   # skip explicit ids in use
                self._next_rid += 1
            rid = self._next_rid
            self._next_rid += 1
        elif rid in self._rids:
            raise ValueError(f"duplicate request id {rid!r}")
        hashes = (lm.prompt_block_hashes(prompt, self.block_size)
                  if self.prefix_cache else None)
        self.scheduler.submit(Request(rid=rid, prompt=prompt,
                                      max_new_tokens=max_new_tokens,
                                      arrival=arrival, eos_id=eos_id,
                                      frontend_emb=frontend_emb,
                                      block_hashes=hashes,
                                      sampling=sampling))
        self._rids.add(rid)          # only after validation succeeded
        return rid

    # -- serving loop --------------------------------------------------------------
    def prefill_compiles(self) -> int:
        """Total prefill compilations so far (whole + bucketed + chunked) —
        with bucketing this is bounded by the bucket count; with chunked
        prefill it is exactly 1 once any prompt has been processed."""
        fns = [self._prefill, self._prefill_b, getattr(self, "_chunk", None)]
        return sum(f._cache_size() for f in fns if f is not None)

    def _full_prefill(self, prompt_len: int, prompt, frontend_emb,
                      sample_args) -> tuple:
        """Whole-prompt prefill into the dense scratch cache; returns
        (first token [1], populated single-request cache).
        ``frontend_emb`` is the request's [1, F, frontend_dim] embeddings
        (None for decoder-only archs); ``sample_args`` the lane's
        first-token sampling scalars (argmax-bitwise for greedy lanes)."""
        if self.bucket_prompts:
            sb = bucket_length(prompt_len, self.kv_len)
            padded = jnp.zeros((1, sb), jnp.int32).at[0, :prompt_len].set(prompt)
            return self._prefill_b(self.params, self._fresh, padded,
                                   jnp.asarray(prompt_len, jnp.int32),
                                   frontend_emb, sample_args)
        return self._prefill(self.params, self._fresh, prompt[None],
                             frontend_emb, sample_args)

    def _set_lane_sampling(self, slot: int, act: ActiveSlot) -> None:
        """Publish the admitted request's sampling configuration to lane
        ``slot``: host-side params + base key for the per-lane speculative
        path, and the vectorized per-slot arrays the batched decode steps
        consume."""
        sp = act.request.sampling or GREEDY
        base = sp.base_key()
        self._samp[slot] = sp
        self._skey_host[slot] = base
        self._skeys = self._skeys.at[slot].set(base)
        self._temp = self._temp.at[slot].set(sp.temperature)
        self._topk = self._topk.at[slot].set(sp.top_k)
        self._topp = self._topp.at[slot].set(sp.top_p)

    def _first_token_args(self, slot: int, position: int) -> tuple:
        """Sampling scalars for the token a prefill emits at cache
        ``position`` (the key depends only on seed + position, so chunked,
        bucketed and whole prefills of the same request draw the same
        token)."""
        sp = self._samp[slot]
        return (sampling_mod.token_key(self._skey_host[slot], position),
                jnp.asarray(sp.temperature, jnp.float32),
                jnp.asarray(sp.top_k, jnp.int32),
                jnp.asarray(sp.top_p, jnp.float32))

    def _refresh_row(self, slot: int, group: str) -> jax.Array:
        """Rebuild ``slot``'s published table row for ``group`` from the
        allocator's current tables."""
        if group == "global":
            row = self.allocator.padded_table(slot, self._max_blocks)
        elif group == "cross":
            row = self.allocator.padded_cross_table(slot, self._cross_width)
        else:
            row = self.allocator.padded_window_table(slot, self._max_blocks)
        arr = jnp.asarray(row, jnp.int32)
        self._rows[slot][group] = arr
        return arr

    def _activate_lane(self, slot: int, tok, start_pos: int) -> None:
        """Bring a freshly prefilled request online in decode lane ``slot``
        (paged regime: also publish its group table rows to the decode
        step)."""
        self._toks, self._pos, self._tables = self._lane_set(
            self._toks, self._pos, self._tables,
            jnp.asarray(slot, jnp.int32), tok,
            jnp.asarray(start_pos, jnp.int32), self._rows[slot])
        self._host_pos[slot] = start_pos

    def _admit_one(self, act: ActiveSlot) -> None:
        slot = act.slot
        prompt_len = act.request.prompt_len
        prompt = jnp.asarray(act.request.prompt, jnp.int32)
        fe = act.request.frontend_emb
        fe1 = None if fe is None else fe[None]
        # the decode lane starts past everything resident: the prompt,
        # plus a VLM frontend's projected rows ahead of it
        start_pos = self._frontend_extra + prompt_len
        self._set_lane_sampling(slot, act)
        sargs = self._first_token_args(slot, start_pos)
        if not self.paged:
            tok, cache = self._full_prefill(prompt_len, prompt, fe1, sargs)
            self._caches, self._toks, self._pos = self._insert(
                self._caches, cache, self._toks, self._pos,
                jnp.asarray(slot, jnp.int32), tok[0],
                jnp.asarray(start_pos, jnp.int32))
            act.first_token_step = self._now
            act.tokens.append(int(tok[0]))
            return
        # prefix-cache hit: positions below ``skip`` are already resident
        # in shared blocks.  At least one position must be recomputed so
        # the first-token logits exist, hence the prompt_len - 1 cap; when
        # the cap pulls the first recomputed position back INTO a shared
        # block (whole-prompt block-aligned hit), that block is forked
        # copy-on-write before the write lands.
        skip = 0
        if self.prefix_cache:
            matched = self.allocator.matched_tokens.get(slot, 0)
            skip = min(matched, prompt_len - 1)
            if matched > skip:
                pair = self.allocator.ensure_private(
                    slot, skip // self.block_size)
                if pair is not None:
                    src, dst = pair
                    self._caches = self._copy_block(
                        self._caches, jnp.asarray(src, jnp.int32),
                        jnp.asarray(dst, jnp.int32))
        self._rows[slot] = {}
        for group in self._tables:
            self._refresh_row(slot, group)
        if self.prefill_chunk:
            # defer: one chunk per engine step, interleaved with decode.
            # A reused lane still holds the previous occupant's recurrent
            # state — zero it before the chunks start carrying state in
            # (full prefill resets it via the insert instead).
            if self._has_state:
                self._caches = self._reset_state(
                    self._caches, self._fresh, jnp.asarray(slot, jnp.int32))
            if self._has_cross:
                # encode-at-admission: the cross block set is written once
                # here and is read-only for the request's lifetime
                cross_single = self._encode_cross(self.params, fe1)
                self._caches = self._insert_cross(
                    self._caches, cross_single, self._rows[slot]["cross"])
            if self._frontend_extra:
                # frontend rows ride the chunk stream as precomputed
                # embedding rows (a chunk may straddle the boundary)
                item = lm.embed_prompt_rows(self.cfg, self.params, prompt,
                                            fe)
            else:
                item = prompt
            self._prefilling[slot] = [item, 0, skip]
            return
        # whole-prompt prefill recomputes everything (memory sharing only:
        # the insert masks writes below ``skip`` so shared blocks stay
        # read-only); the chunked path above also skips the *compute*
        tok, cache = self._full_prefill(prompt_len, prompt, fe1, sargs)
        self._caches = self._insert_p(self._caches, cache, self._rows[slot],
                                      jnp.asarray(slot, jnp.int32),
                                      jnp.asarray(skip, jnp.int32))
        if self.prefix_cache:
            self.allocator.commit_slot(slot)
        self._activate_lane(slot, tok[0], start_pos)
        act.first_token_step = self._now
        act.tokens.append(int(tok[0]))

    def _run_chunk(self, slot: int) -> bool:
        """Advance ``slot``'s chunked prefill by one chunk; returns True
        (and activates the decode lane) when the prompt is fully resident.
        The chunk stream is token ids, or precomputed embedding rows for a
        modality-frontend arch (``total`` then counts frontend rows too)."""
        item, done, skip = self._prefilling[slot]
        C = self.prefill_chunk
        start = skip + done * C    # prefix-cache hit: skip cached positions
        total = item.shape[0]
        valid = min(C, total - start)          # real rows in this slice
        act = self.scheduler.active[slot]
        rid = act.request.rid
        spans = self._spans
        spans.chunks.append((rid, start, valid, total))
        with spans.span("engine.chunk", rid=rid, start=start, valid=valid):
            piece = item[start:start + C]
            if valid < C:                      # pad final chunk to C
                piece = jnp.zeros((C,) + item.shape[1:],
                                  item.dtype).at[:valid].set(piece)
            if self._has_window:
                # slide the ring to cover this slice; rows behind the
                # slice's FIRST query keep their window (freed only once
                # fully behind)
                fresh, freed = self.allocator.extend_window(
                    slot, min(start + C, total), first_query_pos=start)
                if fresh or freed:
                    self._refresh_row(slot, "window")
            last = total - 1 - start           # only valid on the final chunk
            tok, self._caches, load = self._chunk(
                self.params, self._caches, piece[None],
                jnp.asarray(start, jnp.int32), self._rows[slot],
                jnp.asarray(min(max(last, 0), C - 1), jnp.int32),
                jnp.asarray(slot, jnp.int32), jnp.asarray(valid, jnp.int32),
                self._first_token_args(slot, total))
            if load is not None:
                self._loads.append(load)
            self._prefilling[slot][1] = done + 1
            if start + C < total:
                return False
            del self._prefilling[slot]
            if self.prefix_cache:
                self.allocator.commit_slot(slot)
            self._activate_lane(slot, tok[0], total)
            act.first_token_step = self._now
        # the first token's device -> host sync, apart from the dispatch
        with spans.span("engine.sync", what="chunk"):
            act.tokens.append(int(self._sync(tok)[0]))
        return True

    def _finish(self, slot: int) -> list:
        """Retire ``slot`` (reclaims blocks and its recurrent state slot;
        paged: unmap its table rows)."""
        with self._spans.span("engine.finish",
                              rid=self.scheduler.active[slot].request.rid):
            act = self.scheduler.finish(slot)
            self._samp.pop(slot, None)
            self._skey_host.pop(slot, None)
            if self.paged:
                for group in self._tables:
                    self._tables[group] = self._tables[group].at[slot].set(
                        self._null_rows[group])
                self._rows.pop(slot, None)
                self._host_pos.pop(slot, None)
        return act.tokens

    def _grow_tables(self, decoding: list) -> None:
        """Paged: claim the block backing each lane's next write *before*
        the decode step runs — the write needs a physical destination, so
        growth is eager here where dense accounting could stay lazy.
        Window rings additionally free every block that has fallen fully
        behind ``pos - window`` back to the allocator."""
        for slot in decoding:
            n_res = self._host_pos[slot] + 1
            if self._has_global:
                if self.allocator.extend(slot, n_res):
                    row = self._refresh_row(slot, "global")
                    self._tables["global"] = \
                        self._tables["global"].at[slot].set(row)
            if self._has_window:
                fresh, freed = self.allocator.extend_window(slot, n_res)
                if fresh or freed:
                    row = self._refresh_row(slot, "window")
                    self._tables["window"] = \
                        self._tables["window"].at[slot].set(row)

    def _pick_victim(self) -> Optional[int]:
        """Youngest active slot (latest admission, slot id breaking ties) —
        preempting the youngest discards the least work, and requeueing it
        at the queue head under strict FCFS keeps completion order (and
        greedy-decode tokens) identical to an uninterrupted run.  None
        when at most one slot is active: evicting the only lane cannot
        free enough for its own re-admission to fare better, so the caller
        should let ``CacheExhausted`` propagate."""
        if len(self.scheduler.active) <= 1:
            return None
        return max(self.scheduler.active.values(),
                   key=lambda a: (a.admitted_at, a.slot)).slot

    def _preempt(self, slot: int) -> None:
        """Evict ``slot`` mid-flight (the lazy-pricing ``CacheExhausted``
        safety net): discard its generated tokens, requeue its request at
        the queue head, reclaim its cache blocks, and null its published
        table rows so the decode step cannot touch freed pages."""
        self.scheduler.preempt(slot)
        self._prefilling.pop(slot, None)
        self._samp.pop(slot, None)
        self._skey_host.pop(slot, None)
        if self.paged:
            for group in self._tables:
                self._tables[group] = self._tables[group].at[slot].set(
                    self._null_rows[group])
            self._rows.pop(slot, None)
            self._host_pos.pop(slot, None)

    def _speculative_round(self, slot: int) -> Optional[tuple]:
        """One self-speculative round for decode lane ``slot``.

        Protocol (docs/serving.md §sampling): grow the lane's tables to
        cover the draft window; snapshot its recurrent state; draft up to
        ``speculate`` tokens with the truncated-layer step (each lands its
        K/V through the lane's tables); restore the state and verify all
        drafts in one chunk-shaped full-model step; accept by rejection
        sampling (exact argmax agreement under greedy); then rewind —
        truncate the block-table tail and window ring past the accepted
        window and, on partial acceptance, restore the state snapshot
        again and settle it with a ``valid = accepted + 1`` pass.

        Returns ``(emitted tokens, n_drafted, n_accepted)``, or None when
        the lane itself was preempted while growing its tables (lazy
        pricing)."""
        act = self.scheduler.active[slot]
        sp = self._samp[slot]
        pos = self._host_pos[slot]
        budget = act.request.max_new_tokens - len(act.tokens)
        k_r = max(0, min(self.speculate, budget - 1,
                         self._kv_total - pos - 1))
        while True:
            try:
                if self._has_global and self.allocator.extend(
                        slot, pos + k_r + 1):
                    self._refresh_row(slot, "global")
                if self._has_window:
                    fresh, freed = self.allocator.extend_window(
                        slot, pos + k_r + 1, first_query_pos=pos)
                    if fresh or freed:
                        self._refresh_row(slot, "window")
                break
            except CacheExhausted:
                victim = self._pick_victim()
                if victim is None:
                    raise
                self._preempt(victim)
                if victim == slot:
                    return None
        rows = self._rows[slot]
        slot_arr = jnp.asarray(slot, jnp.int32)
        base = self._skey_host[slot]
        temp = jnp.asarray(sp.temperature, jnp.float32)
        topk = jnp.asarray(sp.top_k, jnp.int32)
        topp = jnp.asarray(sp.top_p, jnp.float32)
        snap = None
        if self._has_state and k_r:
            snap = self._snapshot(self._caches, slot_arr)
        draft_toks: list[int] = []
        draft_probs: list[jax.Array] = []
        tok = jnp.asarray(act.tokens[-1], jnp.int32)
        for i in range(k_r):
            dkey = sampling_mod.token_key(base, pos + i + 1,
                                          sampling_mod.STREAM_DRAFT)
            tok, q, self._caches = self._draft_step(
                self.params, self._caches, tok,
                jnp.asarray(pos + i, jnp.int32), rows, slot_arr, dkey,
                temp, topk, topp)
            draft_toks.append(int(tok))
            draft_probs.append(q)
        if snap is not None:
            # the draft advanced the lane's recurrent state k_r tokens;
            # the verify pass must start from the pre-draft state
            self._caches = self._restore(self._caches, snap, slot_arr)
        width = self.speculate + 1
        toks_arr = np.zeros((width,), np.int32)
        toks_arr[0] = act.tokens[-1]
        toks_arr[1:1 + k_r] = draft_toks
        logits, self._caches = self._verify_step(
            self.params, self._caches, jnp.asarray(toks_arr),
            jnp.asarray(pos, jnp.int32), rows, slot_arr,
            jnp.asarray(k_r + 1, jnp.int32))
        pad = [jnp.zeros((self.cfg.vocab_size,), jnp.float32)] \
            * (self.speculate - k_r)
        akey = sampling_mod.token_key(base, pos + 1,
                                      sampling_mod.STREAM_ACCEPT)
        n_acc, nxt = self._accept(
            logits, jnp.stack(draft_probs + pad),
            jnp.asarray(np.pad(np.asarray(draft_toks, np.int32),
                               (0, self.speculate - k_r))),
            jnp.asarray(k_r, jnp.int32), akey, temp, topk, topp)
        a, e = int(n_acc), int(nxt)
        if snap is not None and a < k_r:
            # partial acceptance: the verify pass advanced the state over
            # all k_r + 1 rows — re-run it from the snapshot with only the
            # accepted rows valid to settle the exact post-accept state
            self._caches = self._restore(self._caches, snap, slot_arr)
            _, self._caches = self._verify_step(
                self.params, self._caches, jnp.asarray(toks_arr),
                jnp.asarray(pos, jnp.int32), rows, slot_arr,
                jnp.asarray(a + 1, jnp.int32))
        final_res = pos + a + 1
        if a < k_r:
            if self._has_global and self.allocator.truncate(slot, final_res):
                self._refresh_row(slot, "global")
            if self._has_window and self.allocator.truncate_window(
                    slot, final_res):
                self._refresh_row(slot, "window")
        self._host_pos[slot] = final_res
        return draft_toks[:a] + [e], k_r, a

    def run(self, max_steps: Optional[int] = None) -> dict:
        """Serve every queued request to completion. Returns
        {rid: [generated token ids]} (the prefill token included).

        The engine clock (``self.now``) persists across calls, so arrivals
        are absolute engine steps and a ``max_steps``-bounded run can be
        resumed by calling ``run()`` again."""
        results: dict = {}
        steps = 0
        while self.scheduler.has_work():
            if max_steps is not None and steps >= max_steps:
                break
            now = self._now
            with TraceAnnotation("engine.step", step=now):
                ran = self._step(now, results)
            if ran is None:
                break
            steps += ran
        return results

    def _step(self, now: int, results: dict) -> Optional[bool]:
        """One step of ``run()`` at engine clock ``now``: admit, run one
        chunk per prefilling lane, decode the running lanes, retire the
        finished ones into ``results``, and record the step.  Each phase
        is a span of ``self._spans`` (docs/serving.md, "Spans and step
        records").  Returns True for a recorded step, False when nothing
        could run and the clock jumped to the next arrival, None when
        nothing ever will."""
        spans = self._spans = StepSpans(now)
        prefills = 0                           # completed (one token each)
        for act in self.scheduler.admit(now):
            rid = act.request.rid
            spans.admitted.append(rid)
            with spans.span("engine.admit", rid=rid):
                self._admit_one(act)
            if act.slot in self._prefilling:
                continue                       # chunked: no token yet
            prefills += 1
            if act.is_finished():              # max_new == 1 or prompt-EOS
                results[rid] = self._finish(act.slot)
        # chunked prefills: one chunk per prefilling slot per step,
        # interleaved with the decode of running lanes below
        for slot in sorted(self._prefilling):
            if self._run_chunk(slot):
                prefills += 1                  # final chunk emitted a token
                act = self.scheduler.active[slot]
                if act.is_finished():
                    results[act.request.rid] = self._finish(slot)

        decoding = sorted(s for s in self.scheduler.active
                          if s not in self._prefilling)
        if not decoding:
            if prefills or spans.chunks:       # all work this step was prefill
                self._record_step((), prefills, 0)
                self._now = now + 1
                return True
            nxt = self.scheduler.next_arrival()
            if nxt is None:
                return None
            if nxt <= now and not self.scheduler.active:
                # the queue head has arrived, nothing is running that
                # could ever free capacity, and admission still refused
                # it: the request can never fit.  Fail loudly instead
                # of spinning the idle-jump forever.
                head = self.scheduler._pending[0]
                raise CacheExhausted(
                    f"request {head.rid!r} (prompt {head.prompt_len} + "
                    f"max_new {head.max_new_tokens}) can never be "
                    f"admitted: the empty pool "
                    f"({self.allocator.n_blocks} blocks) is too small "
                    f"for its admission price")
            self._now = max(now + 1, nxt)      # idle: jump to next arrival
            return False

        if self.paged and self.speculate:
            # self-speculative decode: one per-lane round per step —
            # draft, verify in one batched chunk-shaped step, accept,
            # rewind (growth happens inside the round, per lane)
            drafted = accepted = rewound = new_tokens = 0
            ran = []
            for slot in decoding:
                act = self.scheduler.active.get(slot)
                if act is None:
                    continue           # preempted by an earlier round
                rid = act.request.rid
                pos = self._frontend_extra + act.position - 1
                with spans.span("engine.speculate", rid=rid):
                    out = self._speculative_round(slot)
                if out is None:
                    continue           # the lane itself was preempted
                ran.append(slot)
                spans.decoded.append((rid, pos))
                emitted, k_r, a = out
                drafted += k_r
                accepted += a
                rewound += k_r - a
                for t in emitted:
                    act.tokens.append(t)
                    new_tokens += 1
                    if act.is_finished():
                        break          # EOS inside the accepted window
                if act.is_finished():
                    results[rid] = self._finish(slot)
            self._record_step(ran, prefills, new_tokens, drafted=drafted,
                              accepted=accepted, rewound=rewound)
            self._now = now + 1
            return True

        if self.paged:
            with spans.span("engine.grow_tables"):
                while True:
                    try:
                        self._grow_tables(decoding)
                        break
                    except CacheExhausted:
                        # lazy pricing's mid-decode OOM: preempt the
                        # youngest slot and retry (extend is idempotent
                        # for the already-grown lanes)
                        victim = self._pick_victim()
                        if victim is None:
                            raise
                        self._preempt(victim)
                        decoding = [s for s in decoding if s != victim]
            if not decoding:               # every decoding lane was evicted
                self._record_step((), prefills, 0)
                self._now = now + 1
                return True
        with spans.span("engine.decode", lanes=len(decoding)):
            if self.paged:
                active = np.zeros((self.n_slots,), bool)
                active[decoding] = True
                toks, self._caches = self._decode_p(
                    self.params, self._caches, self._toks, self._pos,
                    self._tables, jnp.asarray(active),
                    (self._skeys, self._temp, self._topk, self._topp))
            else:
                toks, self._caches = self._decode(
                    self.params, self._caches, self._toks, self._pos,
                    self._skeys, self._temp, self._topk, self._topp)
            self._toks = toks
            self._pos = self._pos + 1
        with spans.span("engine.sync", what="decode"):
            toks_host = self._sync(toks)       # one device->host transfer
        new_tokens = 0
        for slot in decoding:
            act = self.scheduler.active.get(slot)
            if act is None:
                continue               # preempted by an earlier lane
            spans.decoded.append((act.request.rid,
                                  self._frontend_extra + act.position - 1))
            act.tokens.append(int(toks_host[slot]))
            new_tokens += 1
            if self.paged:
                self._host_pos[slot] += 1
            else:
                # cache entries resident after this step: prompt + all
                # decode writes so far (the just-emitted token is not
                # yet written); paged growth happened eagerly above
                preempted_self = False
                while True:
                    try:
                        self.allocator.extend(slot, act.position - 1)
                        break
                    except CacheExhausted:
                        victim = self._pick_victim()
                        if victim is None:
                            raise
                        self._preempt(victim)
                        if victim == slot:
                            preempted_self = True
                            break
                if preempted_self:
                    new_tokens -= 1    # its token was discarded
                    spans.decoded.pop()
                    continue
            if act.is_finished():
                results[act.request.rid] = self._finish(slot)
        self._record_step(decoding, prefills, new_tokens)
        self._now = now + 1
        return True

    def _record_step(self, active_slots, prefills: int, new_tokens: int,
                     drafted: int = 0, accepted: int = 0,
                     rewound: int = 0) -> None:
        """Close the step in progress into a ``ServeStep``.  Host
        arithmetic only: nothing here runs on the device."""
        spans = self._spans
        with spans.span("engine.record"):
            by_group = self.allocator.resident_bytes_by_group()
            # per-step deltas of the cumulative ledgers
            stats = self.allocator.stats
            cur = (self.scheduler.preemptions, stats["hit_tokens"],
                   stats["lookup_tokens"])
            prev = self._stats_last
            self._stats_last = cur
            shared = self.allocator.shared_saved_bytes()
            cached = self.allocator.cached_blocks()
            experts, self._step_load = self._step_load, None
        t1 = time.perf_counter()
        self.telemetry.record_step(
            step=spans.step, seconds=t1 - spans.t0,
            active_slots=active_slots, n_slots=self.n_slots,
            blocks_in_use=self.allocator.n_in_use,
            n_blocks=self.allocator.n_blocks,
            prefills=prefills, prefill_chunks=len(spans.chunks),
            new_tokens=new_tokens,
            resident_bytes=sum(by_group.values()),
            capacity_bytes=self._capacity_bytes,
            resident_by_group=by_group if self.paged else None,
            preemptions=cur[0] - prev[0],
            prefix_hit_tokens=cur[1] - prev[1],
            prefix_lookup_tokens=cur[2] - prev[2],
            shared_saved_bytes=shared, cached_blocks=cached,
            drafted=drafted, accepted=accepted, rewound_tokens=rewound,
            t0=spans.t0, t1=t1, phases=spans.phases,
            admitted=spans.admitted, chunks=spans.chunks,
            decoded=spans.decoded,
            experts=None if experts is None else tuple(experts.tolist()))
