"""Physical paged serving: PagedKVStore storage, paged-engine token identity
against the static ``Engine`` oracle, prompt-length bucketing (bounded
compile count), and chunked prefill interleaving."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get
from repro.models import lm
from repro.serve import (BlockAllocator, CacheConfig, CacheLayout,
                         ContinuousEngine, Engine, PagedKVStore,
                         bucket_length)


# =============================================================================
# physical store
# =============================================================================

def test_store_write_gather_roundtrip_across_block_boundary():
    cfg = CacheConfig(block_size=4, n_blocks=8)
    store = PagedKVStore(cfg, n_layers=2, n_kv_heads=2, head_dim=8)
    alloc = BlockAllocator(cfg, store=store)
    alloc.allocate(slot=0, n_tokens=3)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    krows = jax.random.normal(k1, (6, 2, 2, 8))      # 6 tokens, [L, KV, hd]
    vrows = jax.random.normal(k2, (6, 2, 2, 8))
    for pos in range(3):
        alloc.write_token(0, pos, krows[pos], vrows[pos])
    alloc.extend(0, 6)                               # crosses into block 2
    for pos in range(3, 6):
        alloc.write_token(0, pos, krows[pos], vrows[pos])
    k, v = alloc.gather_slot(0)                      # [L, 6, KV * hd]
    assert k.shape == (2, 6, 16)
    for pos in range(6):
        assert jnp.all(k[:, pos] == krows[pos].reshape(2, 16)), pos
        assert jnp.all(v[:, pos] == vrows[pos].reshape(2, 16)), pos
    alloc.free_slot(0)
    alloc.check_no_leaks()


def test_store_residency_accounting():
    cfg = CacheConfig(block_size=4, n_blocks=8)
    store = PagedKVStore(cfg, n_layers=3, n_kv_heads=2, head_dim=8,
                         dtype=jnp.float32)
    alloc = BlockAllocator(cfg, store=store)
    per_block = 2 * 3 * 4 * 2 * 8 * 4                # K+V, L*bs*KV*hd*f32
    assert store.block_bytes == per_block
    assert alloc.capacity_bytes() == 8 * per_block
    alloc.allocate(0, 10)                            # 3 blocks
    assert alloc.resident_bytes() == 3 * per_block
    alloc.free_slot(0)
    assert alloc.resident_bytes() == 0


def test_padded_table_uses_null_block():
    cfg = CacheConfig(block_size=4, n_blocks=8)
    alloc = BlockAllocator(cfg)
    blocks = alloc.allocate(0, 6)
    row = alloc.padded_table(0, 5)
    assert row[:2] == blocks and row[2:] == [cfg.null_block] * 3
    with pytest.raises(ValueError):
        alloc.padded_table(0, 1)
    alloc.free_slot(0)


# =============================================================================
# engine gating
# =============================================================================

def test_paged_serves_every_decoder_only_arch():
    """The old whole-model gate is gone: paged mode now builds mixed layer
    groups from the per-layer capability report, so recurrent/window archs
    construct (token identity is the arch-matrix suite's job)."""
    for arch in ("mamba2-370m", "mixtral-8x7b", "recurrentgemma-2b"):
        cfg = get(arch).reduced()
        key = jax.random.PRNGKey(0)
        params = lm.init_params(cfg, key, jnp.float32)
        eng = ContinuousEngine(cfg, params, kv_len=32, paged=True)
        groups = lm.serve_groups(cfg)
        assert eng._has_window == bool(groups["window"]), arch
        assert eng._has_state == bool(groups["recurrent"]), arch


def test_chunked_prefill_requires_paged():
    cfg = get("paper-mlp").reduced()
    with pytest.raises(ValueError):
        ContinuousEngine(cfg, params={}, kv_len=32, prefill_chunk=8)


def test_paged_requires_block_aligned_kv_len():
    cfg = get("paper-mlp").reduced()
    with pytest.raises(ValueError):
        ContinuousEngine(cfg, params={}, kv_len=30, block_size=16, paged=True)


# =============================================================================
# token identity (the acceptance bar: paged + bucketing + chunking all equal
# per-request greedy decode from the static Engine oracle)
# =============================================================================

def _setup(arch, kv_len=64, n_prompts=5, seed=0):
    cfg = get(arch).reduced()
    key = jax.random.PRNGKey(seed)
    params = lm.init_params(cfg, key, jnp.float32)
    lens = [5 + (3 * i) % 11 for i in range(n_prompts)]
    prompts = [jax.random.randint(jax.random.fold_in(key, i), (lens[i],), 0,
                                  cfg.vocab_size) for i in range(n_prompts)]
    budgets = [4 + i % 3 for i in range(n_prompts)]
    ref = Engine(cfg, params, kv_len=kv_len)
    expects = [ref.generate(p[None], max_new_tokens=b)[0].tolist()
               for p, b in zip(prompts, budgets)]
    return cfg, params, prompts, budgets, expects


@pytest.mark.parametrize("arch", ["paper-mlp", "tinyllama-1.1b"])
def test_paged_matches_per_request_greedy(arch):
    cfg, params, prompts, budgets, expects = _setup(arch)
    eng = ContinuousEngine(cfg, params, kv_len=64, n_slots=2, paged=True)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=budgets[i], rid=i, arrival=i)
    results = eng.run()
    for i in range(len(prompts)):
        assert results[i] == expects[i], (arch, i)
    assert eng.telemetry.peak_resident_bytes() > 0   # physical pages pinned
    eng.allocator.check_no_leaks()
    assert eng.allocator.resident_bytes() == 0


def test_paged_bucketed_matches_and_bounds_compiles():
    cfg, params, prompts, budgets, expects = _setup("paper-mlp", n_prompts=7)
    eng = ContinuousEngine(cfg, params, kv_len=64, n_slots=2, paged=True,
                           bucket_prompts=True)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=budgets[i], rid=i, arrival=i)
    results = eng.run()
    for i in range(len(prompts)):
        assert results[i] == expects[i], i
    # compile count bounded by the bucket count, not distinct prompt lengths
    distinct = {p.shape[0] for p in prompts}
    buckets = {bucket_length(n, 64) for n in distinct}
    assert len(buckets) < len(distinct)
    assert eng.prefill_compiles() == len(buckets)
    eng.allocator.check_no_leaks()


def test_dense_bucketed_matches_and_bounds_compiles():
    """Bucketing is independent of the physical regime: the dense engine
    gets the same compile bound with position-masked pad rows."""
    cfg, params, prompts, budgets, expects = _setup("paper-mlp", n_prompts=7)
    eng = ContinuousEngine(cfg, params, kv_len=64, n_slots=2,
                           bucket_prompts=True)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=budgets[i], rid=i, arrival=i)
    results = eng.run()
    for i in range(len(prompts)):
        assert results[i] == expects[i], i
    buckets = {bucket_length(p.shape[0], 64) for p in prompts}
    assert eng.prefill_compiles() == len(buckets)
    eng.allocator.check_no_leaks()


def test_chunked_prefill_matches_and_compiles_once():
    cfg, params, prompts, budgets, expects = _setup("paper-mlp", n_prompts=5)
    eng = ContinuousEngine(cfg, params, kv_len=64, n_slots=2, paged=True,
                           prefill_chunk=8)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=budgets[i], rid=i, arrival=i)
    results = eng.run()
    for i in range(len(prompts)):
        assert results[i] == expects[i], i
    assert eng.prefill_compiles() == 1               # one chunk shape, ever
    eng.allocator.check_no_leaks()


def test_chunked_prefill_interleaves_with_decode():
    """A long prompt arriving mid-stream must not stall the running lane:
    some engine steps carry both a prefill chunk and decoded tokens."""
    cfg = get("paper-mlp").reduced()
    key = jax.random.PRNGKey(1)
    params = lm.init_params(cfg, key, jnp.float32)
    short = jax.random.randint(jax.random.fold_in(key, 0), (4,), 0,
                               cfg.vocab_size)
    long = jax.random.randint(jax.random.fold_in(key, 1), (33,), 0,
                              cfg.vocab_size)
    eng = ContinuousEngine(cfg, params, kv_len=64, n_slots=2, paged=True,
                           prefill_chunk=8)
    eng.submit(short, max_new_tokens=12, rid="short", arrival=0)
    eng.submit(long, max_new_tokens=3, rid="long", arrival=1)
    results = eng.run()

    ref = Engine(cfg, params, kv_len=64)
    assert results["short"] == ref.generate(short[None], 12)[0].tolist()
    assert results["long"] == ref.generate(long[None], 3)[0].tolist()
    mixed = [s for s in eng.telemetry.steps
             if s.prefill_chunks > 0 and s.new_tokens > 0]
    assert mixed, "no step interleaved a prefill chunk with decode"
    # chunk work units are not tokens: totals must count only emitted ones
    assert eng.telemetry.total_tokens() == sum(
        len(v) for v in results.values())
    eng.allocator.check_no_leaks()


def test_chunked_prefill_pad_rows_cannot_clobber_resident_blocks():
    """Regression: when the chunk size does not divide kv_len, the final
    chunk's pad rows reach positions past the table's range; they must be
    redirected to the null page, not clamped onto the last real block
    (which holds resident prompt K/V)."""
    cfg = get("paper-mlp").reduced()
    key = jax.random.PRNGKey(5)
    params = lm.init_params(cfg, key, jnp.float32)
    prompt = jax.random.randint(key, (61,), 0, cfg.vocab_size)
    eng = ContinuousEngine(cfg, params, kv_len=64, n_slots=1, paged=True,
                           prefill_chunk=12)      # 12 does not divide 64
    eng.submit(prompt, max_new_tokens=3, rid=0)
    results = eng.run()
    ref = Engine(cfg, params, kv_len=64)
    assert results[0] == ref.generate(prompt[None], 3)[0].tolist()
    eng.allocator.check_no_leaks()


def test_chunked_prefill_only_request():
    """max_new_tokens == 1 with a chunked prompt: the single token comes
    from the final chunk and the slot retires without ever decoding."""
    cfg = get("paper-mlp").reduced()
    key = jax.random.PRNGKey(2)
    params = lm.init_params(cfg, key, jnp.float32)
    prompt = jax.random.randint(key, (19,), 0, cfg.vocab_size)
    eng = ContinuousEngine(cfg, params, kv_len=32, n_slots=1, paged=True,
                           prefill_chunk=8)
    eng.submit(prompt, max_new_tokens=1, rid=0)
    results = eng.run()
    ref = Engine(cfg, params, kv_len=32)
    assert results[0] == ref.generate(prompt[None], 1)[0].tolist()
    eng.allocator.check_no_leaks()


# =============================================================================
# window block rings (sliding-window layer group)
# =============================================================================

def _window_alloc(n_blocks=16, bs=4, window=8, cap=3, chunk=0):
    a = BlockAllocator(CacheConfig(block_size=bs, n_blocks=n_blocks))
    a.set_layout(CacheLayout(has_global=False, window=window,
                             window_cap_blocks=cap, prefill_chunk=chunk))
    return a


def test_window_ring_slides_and_stays_bounded():
    """Decoding forward forever keeps the ring at O(window) blocks: blocks
    fully behind ``pos - window`` are freed, the retained logical range is
    exactly the window's covering blocks."""
    a = _window_alloc(bs=4, window=8, cap=3)
    a.allocate(0, 6)                       # positions 0..5 -> blocks 0..1
    assert sorted(a.window_tables[0]) == [0, 1]
    peak = 0
    for pos in range(6, 64):
        a.extend_window(0, pos + 1)
        peak = max(peak, len(a.window_tables[0]))
        assert len(a.window_tables[0]) <= 3          # blocks_for(8) + 1
    lo = (63 - 8 + 1) // 4
    assert sorted(a.window_tables[0]) == list(range(lo, 63 // 4 + 1))
    assert peak == 3
    ring_size = len(a.window_tables[0])
    assert a.free_slot(0) == ring_size     # every ring block reclaimed
    a.check_no_leaks()


def test_window_ring_freed_blocks_are_reused():
    """A pool barely larger than one ring serves an arbitrarily long decode:
    every freed-behind-window block cycles back through the free list."""
    a = _window_alloc(n_blocks=4, bs=4, window=8, cap=3)
    a.allocate(0, 6)
    freed_ids: list[int] = []
    claims = 0
    for pos in range(6, 60):               # 15 logical blocks >> 4 physical
        fresh, freed = a.extend_window(0, pos + 1)
        freed_ids += freed
        claims += len(fresh)
        assert set(a.window_tables[0].values()) <= set(range(4))
    assert len(freed_ids) >= 12            # the ring really slid
    # far more claims than the pool holds: freed-behind-window blocks came
    # back through the free list (LIFO — a freed id is the next handed out)
    assert claims > a.n_blocks
    assert set(freed_ids) <= set(range(4))
    a.free_slot(0)
    a.check_no_leaks()


def test_window_ring_random_churn_never_leaks():
    """Random admission/decode-length/retire churn across slots: terminal
    state always returns the pool to fully-free with unique ids."""
    import random

    rng = random.Random(7)
    for trial in range(10):
        a = _window_alloc(n_blocks=32, bs=4, window=12, cap=5)
        live: dict[int, int] = {}
        next_slot = 0
        for _ in range(300):
            op = rng.random()
            if op < 0.35 and len(live) < 6:
                n = rng.randint(1, 10)
                if a.can_allocate(n):
                    a.allocate(next_slot, n)
                    live[next_slot] = n
                    next_slot += 1
            elif op < 0.8 and live:
                slot = rng.choice(sorted(live))
                live[slot] += rng.randint(1, 5)
                a.extend_window(slot, live[slot])
            elif live:
                slot = rng.choice(sorted(live))
                a.free_slot(slot)
                del live[slot]
        for slot in sorted(live):
            a.free_slot(slot)
        a.check_no_leaks()


def test_window_ring_chunked_layout_starts_at_block_zero():
    """With chunked prefill the ring must cover the first chunk's writes
    (block 0 upward), not the prompt's final window — early chunk rows land
    before the window of the last prompt position."""
    a = _window_alloc(bs=4, window=8, cap=5, chunk=8)
    a.allocate(0, 30)                      # prompt 29 + first token
    assert sorted(a.window_tables[0]) == [0, 1]      # first chunk: rows 0..7
    a.extend_window(0, 16, first_query_pos=8)        # second chunk: rows 8..15
    assert 0 in a.window_tables[0]         # pos 1 still in window of query 8
    a.extend_window(0, 24, first_query_pos=16)       # third chunk
    assert 0 not in a.window_tables[0]     # block 0 now fully behind
    a.free_slot(0)
    a.check_no_leaks()


def test_window_residency_bounded_by_window_not_generated_length():
    """Engine-level invariant: a sliding-window arch's peak window-group
    residency is the same for a short and a long generation (O(window)),
    and never exceeds the ring cap."""
    cfg = get("mixtral-8x7b").reduced()    # every layer is sliding-window
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key, jnp.float32)
    prompt = jax.random.randint(key, (6,), 0, cfg.vocab_size)
    peaks = []
    eng = None
    for budget in (40, 90):
        eng = ContinuousEngine(cfg, params, kv_len=128, n_slots=1,
                               paged=True)
        eng.submit(prompt, max_new_tokens=budget, rid=0)
        eng.run()
        eng.allocator.check_no_leaks()
        peaks.append(eng.telemetry.peak_resident_bytes_by_group()["window"])
    assert peaks[0] == peaks[1]
    block_bytes = sum(s.block_bytes for s in eng.allocator.stores)
    assert peaks[1] <= eng._window_cap_blocks() * block_bytes


def test_paged_slot_reuse_after_eos():
    """EOS frees a paged slot early; the next request reuses its physical
    blocks (LIFO free list) and still decodes its own reference tokens."""
    cfg = get("paper-mlp").reduced()
    key = jax.random.PRNGKey(3)
    params = lm.init_params(cfg, key, jnp.float32)
    prompts = [jax.random.randint(jax.random.fold_in(key, 10 + i), (6,), 0,
                                  cfg.vocab_size) for i in range(2)]
    ref = Engine(cfg, params, kv_len=48)
    ref_toks = [ref.generate(p[None], max_new_tokens=8)[0].tolist()
                for p in prompts]

    eos = ref_toks[0][2]
    eng = ContinuousEngine(cfg, params, kv_len=48, n_slots=1, paged=True)
    eng.submit(prompts[0], max_new_tokens=8, rid=0, eos_id=eos)
    eng.submit(prompts[1], max_new_tokens=8, rid=1)
    results = eng.run()
    cut = ref_toks[0].index(eos) + 1
    assert results[0] == ref_toks[0][:cut]
    assert results[1] == ref_toks[1]
    assert eng.scheduler.slot_admissions[0] == 2
    eng.allocator.check_no_leaks()


# =============================================================================
# speculative rewind (block-tail truncation + window-ring rollback +
# recurrent-state snapshot/restore)
# =============================================================================

def test_truncate_frees_whole_tail_blocks_only():
    """Rewind frees only blocks wholly past the kept length; a partially
    vacated tail block stays claimed (its stale rows sit beyond the slot's
    position and are overwritten before they become attendable)."""
    cfg = CacheConfig(block_size=4, n_blocks=8)
    a = BlockAllocator(cfg)
    a.allocate(0, 3)
    a.extend(0, 11)                        # 3 blocks
    assert len(a.tables[0]) == 3
    freed = a.truncate(0, 6)               # keep blocks_for(6) == 2
    assert len(freed) == 1 and len(a.tables[0]) == 2
    a.check()
    assert a.truncate(0, 5) == []          # same covering blocks: no-op free
    assert len(a.tables[0]) == 2
    a.check()
    # freed tail block is the next handed out (LIFO reuse)
    assert a.extend(0, 11) == freed
    a.free_slot(0)
    a.check_no_leaks()


def test_truncate_guards():
    cfg = CacheConfig(block_size=4, n_blocks=8)
    a = BlockAllocator(cfg)
    from repro.serve import AllocatorInvariantError
    with pytest.raises(AllocatorInvariantError):
        a.truncate(0, 2)                   # no allocation
    a.allocate(0, 5)
    with pytest.raises(AllocatorInvariantError):
        a.truncate(0, 9)                   # cannot grow
    a.free_slot(0)
    a.check_no_leaks()


def test_truncate_never_drops_shared_or_indexed_blocks():
    """Rewinding must never free content visible beyond the slot: a
    committed (prefix-indexed) or CoW-shared block in the dropped tail is
    a structural error, not a silent free."""
    from repro.serve import AllocatorInvariantError
    cfg = CacheConfig(block_size=4, n_blocks=16)
    a = BlockAllocator(cfg)
    a.set_layout(CacheLayout(sharable=True))
    hashes = ("h0", "h1")
    a.allocate(0, 8, block_hashes=hashes)
    a.commit_slot(0)                       # both blocks now indexed
    with pytest.raises(AllocatorInvariantError):
        a.truncate(0, 4)                   # would drop indexed block 1
    a.check()                              # guard left the ledgers intact
    # a second slot sharing the prefix: its matched blocks are refcounted
    a.allocate(1, 8, block_hashes=hashes)
    assert a.tables[1][:2] == a.tables[0][:2]
    with pytest.raises(AllocatorInvariantError):
        a.truncate(1, 4)
    a.check()
    a.free_slot(0)
    a.free_slot(1)
    a.check_no_leaks()


def test_truncate_window_rolls_ring_back():
    """Window-ring rollback pops exactly the ring entries past the rewind
    position; the low edge (slid by first_query_pos pinned at the
    pre-draft position) is untouched."""
    a = _window_alloc(n_blocks=16, bs=4, window=8, cap=5)
    a.allocate(0, 6)                       # logical blocks 0..1
    # speculative grow: +6 rows with the query pinned at pos 5
    a.extend_window(0, 12, first_query_pos=5)
    hi = sorted(a.window_tables[0])
    assert hi[-1] == 2                     # rows 6..11 -> logical block 2
    freed = a.truncate_window(0, 7)        # rewind to 7 resident tokens
    assert [i for i in sorted(a.window_tables[0])] == [0, 1]
    assert len(freed) == 1
    a.check()
    a.free_slot(0)
    a.check_no_leaks()


def test_rewind_churn_randomized_never_leaks():
    """Randomized speculative churn: slots admit, grow k+1 rows (the
    draft/verify reservation), rewind to a random acceptance point,
    retire — with the full structural ``check()`` after every rewind.
    Terminal state must return the pool to fully-free."""
    import random

    rng = random.Random(11)
    for trial in range(8):
        cfg = CacheConfig(block_size=4, n_blocks=24)
        a = BlockAllocator(cfg)
        a.set_layout(CacheLayout(window=8, window_cap_blocks=4))
        live: dict[int, int] = {}          # slot -> resident tokens
        next_slot = 0
        for _ in range(200):
            op = rng.random()
            if op < 0.3 and len(live) < 4:
                n = rng.randint(1, 9)
                if a.can_allocate(n):
                    a.allocate(next_slot, n)
                    live[next_slot] = n
                    next_slot += 1
            elif op < 0.85 and live:
                slot = rng.choice(sorted(live))
                pos = live[slot]
                k = rng.randint(1, 4)
                grown = pos + k + 1        # draft k + bonus row
                if not a.can_allocate(grown - pos):
                    continue
                a.extend(slot, grown)
                a.extend_window(slot, grown, first_query_pos=pos - 1)
                accepted = rng.randint(0, k)
                keep = pos + accepted + 1
                a.truncate(slot, keep)
                a.truncate_window(slot, keep)
                a.check()                  # full ledger check every rewind
                live[slot] = keep
            elif live:
                slot = rng.choice(sorted(live))
                a.free_slot(slot)
                del live[slot]
        for slot in sorted(live):
            a.free_slot(slot)
        a.check_no_leaks()


def test_recurrent_state_snapshot_restore_exact():
    """``snapshot_state_lanes`` / ``restore_state_lanes`` must round-trip
    a lane's ssd/rglru scan state bitwise while leaving other lanes and
    non-state entries untouched — the draft pass pollutes, the restore
    erases."""
    cfg = get("mamba2-370m").reduced()
    key = jax.random.PRNGKey(3)
    # the engine's paged tree: state slabs are [repeats, n_slots, ...]
    caches = lm.init_cache(cfg, 3, 16, jnp.float32)
    noise = jax.tree.map(
        lambda x: jax.random.normal(key, x.shape, jnp.float32), caches)
    snap = lm.snapshot_state_lanes(cfg, noise, 1)
    assert jax.tree.leaves(snap)                 # ssd arch has state entries
    polluted = jax.tree.map(lambda x: x + 1.0, noise)
    restored = lm.restore_state_lanes(cfg, polluted, snap, 1)
    for a, b, c in zip(jax.tree.leaves(restored), jax.tree.leaves(noise),
                       jax.tree.leaves(polluted)):
        assert jnp.array_equal(a[:, 1], b[:, 1])  # lane 1: bitwise rollback
        assert jnp.array_equal(a[:, 0], c[:, 0])  # other lanes untouched
        assert jnp.array_equal(a[:, 2], c[:, 2])
    # attention-arch tree has no state entries: snapshot is empty and
    # restore is the identity
    cfg2 = get("paper-mlp").reduced()
    caches2 = lm.init_cache(cfg2, 2, 16, jnp.float32)
    assert not jax.tree.leaves(lm.snapshot_state_lanes(cfg2, caches2, 0))
    r2 = lm.restore_state_lanes(cfg2, caches2,
                                lm.snapshot_state_lanes(cfg2, caches2, 0), 0)
    for a, b in zip(jax.tree.leaves(r2), jax.tree.leaves(caches2)):
        assert jnp.array_equal(a, b)


def test_speculate_rewinds_and_stays_identical_under_prefix_cache():
    """Engine-level rewind bar: speculative greedy decode over a shared
    prefix must stay token-identical to the oracle, rewind only private
    decode-tail rows (never a CoW/committed prompt block — the allocator
    raises if it ever would), and leave the pool structurally sound."""
    cfg = get("tinyllama-1.1b").reduced()
    key = jax.random.PRNGKey(4)
    params = lm.init_params(cfg, key, jnp.float32)
    shared = jax.random.randint(key, (16,), 0, cfg.vocab_size)
    prompts = [jnp.concatenate([shared, jax.random.randint(
        jax.random.fold_in(key, i), (4,), 0, cfg.vocab_size)])
        for i in range(4)]
    ref = Engine(cfg, params, kv_len=64)
    expects = [ref.generate(p[None], max_new_tokens=5)[0].tolist()
               for p in prompts]
    eng = ContinuousEngine(cfg, params, kv_len=64, n_slots=2, paged=True,
                           speculate=3, prefix_cache=True)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=5, rid=i, arrival=i)
    results = eng.run()
    for i in range(len(prompts)):
        assert results[i] == expects[i], i
    assert eng.telemetry.prefix_hit_rate() > 0   # sharing really happened
    assert eng.telemetry.total_drafted() > 0
    eng.allocator.check()


def test_speculate_requires_paged_and_validates():
    cfg = get("paper-mlp").reduced()
    with pytest.raises(ValueError):
        ContinuousEngine(cfg, params={}, kv_len=32, speculate=4)
    with pytest.raises(ValueError):
        ContinuousEngine(cfg, params={}, kv_len=32, paged=True, speculate=-1)


def test_speculate_telemetry_counters_consistent():
    """drafted >= accepted, rewound == drafted - accepted (every rejected
    draft row is rewound), and accept_rate matches the totals."""
    cfg, params, prompts, budgets, expects = _setup("paper-mlp")
    eng = ContinuousEngine(cfg, params, kv_len=64, n_slots=2, paged=True,
                           speculate=4)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=budgets[i], rid=i, arrival=i)
    results = eng.run()
    for i in range(len(prompts)):
        assert results[i] == expects[i], i
    t = eng.telemetry
    drafted = t.total_drafted()
    assert drafted > 0
    accepted = sum(s.accepted for s in t.steps)
    assert 0 <= accepted <= drafted
    assert t.total_rewound_tokens() == drafted - accepted
    assert t.accept_rate() == pytest.approx(
        accepted / drafted if drafted else 0.0)
    eng.allocator.check_no_leaks()
