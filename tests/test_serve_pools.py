"""The paged K/V pools as the engine holds them: lane-dense
``[layers, n_pages, block_size, width]`` stacks that every jitted step
takes donated and hands back updated in place.

Donation is what an accelerator gets (``engine._donates``); these tests
turn it on for the CPU backend too, so that a step that reads a pool it
already gave away fails here as it would on the chip."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get
from repro.models import lm, mla
from repro.serve import ContinuousEngine
from repro.serve import engine as engine_mod

KV_LEN = {"phi-3-vision-4.2b": 56}           # + 8 frontend rows = 64
PAGING = tuple(a for a in ARCH_IDS
               if lm.serve_groups(get(a).reduced())["paged"]
               or lm.serve_groups(get(a).reduced())["window"])
GLOBAL = tuple(a for a in PAGING if lm.serve_groups(get(a).reduced())["paged"])
# (prompt length, max new): four requests through two lanes, so lanes are
# retired and re-admitted while others decode
REQUESTS = ((13, 5), (9, 7), (20, 3), (6, 6))
MODES = {"chunk": {"prefill_chunk": 8}, "whole": {},
         "spec": {"speculate": 2}}
# chunked prefill on every arch that pages; whole-prompt prefill and
# speculative rounds on one arch of each layer-group mix
CASES = ([(a, "chunk") for a in PAGING]
         + [(a, m) for a in ("tinyllama-1.1b", "gemma2-9b",
                             "recurrentgemma-2b") for m in ("whole", "spec")])


@functools.cache
def _model(arch):
    cfg = get(arch).reduced()
    init = jax.jit(lambda key: lm.init_params(cfg, key, jnp.float32))
    return cfg, init(jax.random.PRNGKey(0))


def _engine(arch, donate: bool, monkeypatch, **kw):
    cfg, params = _model(arch)
    with monkeypatch.context() as m:
        m.setattr(engine_mod, "_donates", lambda: donate)
        return ContinuousEngine(cfg, params, kv_len=KV_LEN.get(arch, 64),
                                n_slots=2, paged=True, **kw)


def _frontend(cfg, i):
    if not (cfg.frontend or cfg.n_enc_layers):
        return None
    key = jax.random.fold_in(jax.random.PRNGKey(7), i)
    return jax.random.normal(key, (cfg.frontend_tokens, cfg.frontend_dim))


def _serve(eng):
    for i, (n, new) in enumerate(REQUESTS):
        prompt = [(7 * i + 3 * j) % 50 + 1 for j in range(n)]
        eng.submit(prompt, new, rid=i, arrival=i,
                   frontend_emb=_frontend(eng.cfg, i))
    return eng.run()


@pytest.mark.parametrize("arch,mode", CASES)
def test_donated_engine_serves_retires_and_readmits(arch, mode,
                                                    monkeypatch):
    """Every step that returns the pools consumes the ones it was given:
    an engine that kept a donated pool anywhere would raise on its next
    use.  The tokens are those of the same engine with donation off."""
    got = _serve(_engine(arch, True, monkeypatch, **MODES[mode]))
    want = _serve(_engine(arch, False, monkeypatch, **MODES[mode]))
    assert got == want
    assert sorted(got) == list(range(len(REQUESTS)))


def test_donated_prefix_cache_forks_exports_and_imports(monkeypatch):
    """Copy-on-write forks (``_copy_block``) and a prefix handed from one
    donated engine to another keep the tokens of an undonated engine."""
    prompt = list(range(1, 33))                 # two whole blocks of 16
    runs = []
    for donate in (True, False):
        src = _engine("tinyllama-1.1b", donate, monkeypatch,
                      prefix_cache=True, block_size=16)
        src.submit(prompt, 4, rid="a")
        src.submit(prompt, 4, rid="b", arrival=1)   # hit + CoW fork
        out = src.run()
        dst = _engine("tinyllama-1.1b", donate, monkeypatch,
                      prefix_cache=True, block_size=16)
        hashes = lm.prompt_block_hashes(prompt, 16)
        assert dst.import_prefix_blocks(src.export_prefix_blocks(hashes)) == 2
        dst.submit(prompt, 4, rid="c")
        out.update(dst.run())
        runs.append(out)
    assert runs[0] == runs[1]
    assert runs[0]["a"] == runs[0]["b"] == runs[0]["c"]


def _dense_rows(cfg, single) -> list:
    """(K, V) or (ckv, krope) rows of every global-group layer entry of a
    dense single-request cache, in the engine's store order."""
    out = []
    for si, seg in enumerate(cfg.segments()):
        for ci, spec in enumerate(seg.cycle):
            entry = single[f"seg{si}"][f"c{ci}"]
            if spec.mixer == "global":
                out.append((entry["attn"]["k"], entry["attn"]["v"]))
            elif spec.mixer == "mla":
                out.append((entry["mla"]["ckv"], entry["mla"]["krope"]))
    return out


@pytest.mark.parametrize("arch", GLOBAL)
def test_gather_slot_returns_the_rows_the_steps_wrote(arch, monkeypatch):
    """Whole-prompt prefill: the prompt's rows, gathered back through the
    lane's table from the stores the engine rebinds after each donated
    step, are the dense prefill cache's rows bit for bit; each decode step
    then adds one row at the lane's next position and no further."""
    eng = _engine(arch, True, monkeypatch)
    cfg = eng.cfg
    n = 11
    eng.submit(list(range(1, n + 1)), 8, rid=0,
               frontend_emb=_frontend(cfg, 0))
    eng.run(max_steps=3)                     # admit + decode, then 2 decodes
    slot = next(iter(eng.scheduler.active))
    start = eng._frontend_extra + n          # rows resident before decode
    act = eng.scheduler.active[slot]
    fe = act.request.frontend_emb
    _, single = eng._full_prefill(
        n, jnp.asarray(act.request.prompt, jnp.int32),
        None if fe is None else fe[None], eng._first_token_args(slot, start))
    table = eng.allocator.tables[slot]
    stores = [s for s, g in zip(eng.allocator.stores,
                                eng.allocator.store_groups) if g == "global"]
    dense = _dense_rows(cfg, single)
    assert len(stores) == len(dense) > 0
    for store, pair in zip(stores, dense):
        got = store.gather_slot(table, start + 4)
        for rows, want in zip(got, pair):
            layers = want.shape[0]
            want = np.asarray(want[:, 0, :start]).reshape(layers, start, -1)
            rows = np.asarray(rows)
            # an MLA rope-key row is padded to whole 128-lane rows with
            # zeros (mla.krope_width); every other row is its pool's width
            width = want.shape[-1]
            assert rows.shape[-1] == (mla.krope_width(cfg)
                                      if width == cfg.qk_rope_dim
                                      and cfg.kv_lora_rank else width)
            assert not np.any(rows[..., width:])
            rows = rows[..., :width]
            np.testing.assert_array_equal(rows[:, :start], want)
            # three decode steps wrote positions start .. start + 2
            written = np.abs(rows[:, start:]).sum(axis=(0, 2)) > 0
            assert written.tolist() == [True, True, True, False]


@pytest.mark.parametrize("arch", PAGING)
def test_block_bytes_counts_lane_dense_rows(arch, monkeypatch):
    """One block id pins ``block_size`` rows of every layer of both pools;
    an attention row is ``n_kv_heads * head_dim`` wide, an MLA latent row
    ``kv_lora_rank`` (ckv) and ``qk_rope_dim`` (krope) wide."""
    eng = _engine(arch, True, monkeypatch)
    cfg, bs = eng.cfg, eng.block_size
    itemsize = jnp.dtype(eng.dtype).itemsize
    leaves = lm.paged_cache_leaves(cfg, eng._caches)
    assert len(leaves) == len(eng.allocator.stores) > 0
    for (_, keys, _), store in zip(leaves, eng.allocator.stores):
        if keys[0] == "ckv_pages":
            width = cfg.kv_lora_rank + mla.krope_width(cfg)
            assert mla.krope_width(cfg) % 128 == 0
        else:
            kv_row = cfg.n_kv_heads * cfg.head_dim
            assert store.k_pages.shape[2:] == store.v_pages.shape[2:] \
                == (bs, kv_row)
            width = 2 * kv_row
        assert store.block_bytes == store.n_layers * width * bs * itemsize
