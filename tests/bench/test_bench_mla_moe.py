"""The DeepSeek-V2 family (``bench/models/mla_moe.py``): its reference
against the program at a small size, the share of the experts a chip
holds, its configuration against the program's, and its work counts
against hand numbers."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, window
from bench import engine_adapter as ad
from bench.refmath import seed_key
from bench_helpers import fixture, run, tiny_cell

from repro import configs
from repro.models import lm

MODEL = spec.load_model("mla_moe")
CONF = spec.load_json(spec.BENCH_DIR / "configs" / "deepseek-v2-lite.json")
TINY = fixture("tiny-mla-moe")


def _program(conf, dtype):
    """The program's config and the seeded weights, cast to ``dtype`` (the
    router stays float32)."""
    cfg = ad.program_config(MODEL, conf)
    params = ad.build_params(cfg, MODEL, conf)(7)
    cast = lambda a: a if a.dtype == jnp.float32 else a.astype(dtype)
    return cfg, jax.tree.map(cast, params)


def _served_logits(cfg, params, tokens, n_prompt, chunk, block=16):
    """Logits of the program's serving path for one lane: the prompt in
    ``chunk``-row slices written through a block table into the paged
    pools, then one token at a time decoded against them (each decode
    feeds the next given token, as teacher forcing).  Returns [len, V]
    logits at positions n_prompt - 1 ... len(tokens) - 1."""
    L = len(tokens)
    width = -(-L // block) + 1
    caches = lm.init_paged_caches(cfg, 1, width + 1, block,
                                  jnp.dtype(params["embed"].dtype))
    tables = jnp.arange(width, dtype=jnp.int32)[None]
    out = []
    toks = jnp.asarray(tokens, jnp.int32)
    for start in range(0, n_prompt, chunk):
        valid = min(chunk, n_prompt - start)
        piece = jnp.zeros((1, chunk), jnp.int32).at[0, :valid].set(
            toks[start:start + valid])
        logits, caches, _ = lm.forward(
            cfg, params, piece, positions=start + jnp.arange(chunk),
            cache=caches, mode="prefill", paged_tables=tables,
            moe_lossless=True, valid_len=valid)
    out.append(logits[0, valid - 1])
    for p in range(n_prompt, L):
        logits, caches, _ = lm.forward(
            cfg, params, toks[p].reshape(1, 1), positions=jnp.asarray([p]),
            cache=caches, mode="decode", paged_tables=tables)
        out.append(logits[0, 0])
    return jnp.stack(out)[:, :cfg.vocab_size].astype(jnp.float32)


def _reference_logits(conf, tokens, seed=7):
    key = seed_key(seed)
    h = MODEL.ref_embed(conf, key, jnp.asarray(tokens)[None])
    for layer in range(MODEL.dims(conf)["L"]):
        h = MODEL.ref_layer(conf, key, layer, h, quant=False)
    return MODEL.ref_logits(conf, key, h[0], quant=False)


def test_served_path_matches_the_reference():
    """Chunked prefill (slices of 8, the last one padded) then decode
    through the cache, against the reference's full forward pass, logits
    at every served position.  Float32 weights and activations: the two
    differ by summation order (absorbed against non-absorbed latent
    attention, the grouped expert matmul against per-expert tiles), so
    1e-3 on logits of order one is rounding; the same weights served in
    bfloat16 miss by more than ten times that."""
    conf = TINY
    tokens = np.random.default_rng(0).integers(1, conf["vocab_size"], 45)
    n_prompt = 29
    want = _reference_logits(conf, tokens)[n_prompt - 1:]
    cfg, params = _program(conf, jnp.float32)
    got = _served_logits(cfg, params, tokens, n_prompt, chunk=8)
    assert got.shape == want.shape
    gap = float(jnp.max(jnp.abs(got - want)))
    assert gap < 1e-3, gap
    assert float(jnp.max(jnp.abs(want))) > 0.5
    _, low = _program(conf, jnp.bfloat16)
    rough = _served_logits(cfg, low, tokens, n_prompt, chunk=8)
    assert float(jnp.max(jnp.abs(rough - want))) > 1e-2


def test_reference_shares_sum_to_the_uncut_layer():
    """The reference's routed part over each of the four shares of 8
    experts (2 held each), with the shared experts counted once, adds up
    to the uncut layer holding all 8."""
    conf = TINY
    m = MODEL.dims(conf)
    d, fe = m["d"], m["fe"]
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    full = {"router": jax.random.normal(ks[0], (d, 8)) * d ** -0.5,
            "w_gate": jax.random.normal(ks[1], (8, d, fe)) * d ** -0.5,
            "w_up": jax.random.normal(ks[2], (8, d, fe)) * d ** -0.5,
            "w_down": jax.random.normal(ks[3], (8, fe, d)) * fe ** -0.5}
    n = jax.random.normal(ks[4], (40, d))
    uncut = dict(conf, n_routed_experts=8,
                 deployment=dict(conf["deployment"], expert_parallel=1,
                                 this_chip=0))
    whole = MODEL._routed(uncut, full, n, False)
    parts = 0
    for c in range(4):
        share = dict(conf, deployment=dict(conf["deployment"], this_chip=c))
        w = {**full, **{k: full[k][2 * c:2 * c + 2]
                        for k in ("w_gate", "w_up", "w_down")}}
        parts = parts + MODEL._routed(share, w, n, False)
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(whole))) > 0.01


@pytest.mark.parametrize("chip", [0, 3])
def test_reference_routed_part_token_by_token(chip):
    """The reference's routed part, written out one token at a time: the
    softmax over all 8 experts, the top 2 by probability, and for each
    pair whose expert this chip holds the gate times that expert's
    SwiGLU; pairs on experts held elsewhere add nothing."""
    conf = dict(TINY, deployment=dict(TINY["deployment"], this_chip=chip))
    m = MODEL.dims(conf)
    d, fe = m["d"], m["fe"]
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    w = {"router": jax.random.normal(ks[0], (d, 8)) * d ** -0.5,
         "w_gate": jax.random.normal(ks[1], (2, d, fe)) * d ** -0.5,
         "w_up": jax.random.normal(ks[2], (2, d, fe)) * d ** -0.5,
         "w_down": jax.random.normal(ks[3], (2, fe, d)) * fe ** -0.5}
    n = np.asarray(jax.random.normal(ks[4], (12, d)), np.float64)
    got = MODEL._routed(conf, w, jnp.asarray(n, jnp.float32), False)
    ww = {k: np.asarray(v, np.float64) for k, v in w.items()}
    silu = lambda a: a / (1.0 + np.exp(-a))
    for t in range(n.shape[0]):
        z = n[t] @ ww["router"]
        p = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        want = np.zeros(d)
        for e in np.argsort(-p)[:m["k"]]:
            if m["lo"] <= e < m["lo"] + m["Eh"]:
                j = e - m["lo"]
                h = silu(n[t] @ ww["w_gate"][j]) * (n[t] @ ww["w_up"][j])
                want += p[e] * (h @ ww["w_down"][j])
        # float32 matmuls and sums against the same written out in float64
        np.testing.assert_allclose(np.asarray(got[t]), want, rtol=1e-4,
                                   atol=1e-5)


def test_tiny_cell_is_correct_and_reports():
    """The harness serves the family through the normal path, and its
    correctness check passes; the expert roofline finds no device op on
    the CPU and is left out."""
    res = run(tiny_cell("tiny-mla-moe", "tiny-open"), trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "expert_roofline" not in res["metrics"]


def test_config_file_is_the_program_config():
    """The program's DeepSeek-V2-Lite config holding chip 0's share of
    an expert-parallel-4 deployment; the router's training loss weight is
    not in the published config and stays at its default."""
    assert CONF["reduced"] == ["n_routed_experts"]
    assert CONF["n_routed_experts"] * CONF["deployment"]["expert_parallel"] \
        == CONF["deployment"]["n_routed_experts"] == 64
    want = dataclasses.replace(configs.get("deepseek-v2-lite-16b"),
                               name="deepseek-v2-lite", expert_shard=(0, 4),
                               router_aux_coef=0.0)
    assert ad.program_config(MODEL, CONF) == want


def test_sizes_match_the_program():
    """Parameters by hand (4,910,345,728), equal to the leaves of the
    program's own initialiser at the cell's shapes."""
    assert MODEL.param_count(CONF) == 4_910_345_728
    cfg = ad.program_config(MODEL, CONF)
    tree = jax.eval_shape(lambda: lm.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(tree)) == \
        MODEL.param_count(CONF)
    # the tiny vocabulary (500) is padded to 2,048 rows of the embedding
    # and columns of the head, which the count leaves out
    tiny_cfg = ad.program_config(MODEL, TINY)
    tiny_tree = jax.eval_shape(lambda: lm.init_params(
        tiny_cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    pad = 2 * (tiny_cfg.padded_vocab - tiny_cfg.vocab_size) * 64
    assert sum(a.size for a in jax.tree.leaves(tiny_tree)) - pad == \
        MODEL.param_count(TINY)
    assert MODEL.kv_bytes_per_token(CONF) == 27 * (512 + 64) * 2


def test_token_flops_is_affine_and_chunks_sum():
    f = [MODEL.token_flops(CONF, p, False) for p in (0, 1, 2, 1000)]
    slope = f[1] - f[0]
    assert f[2] - f[1] == pytest.approx(slope)
    assert f[3] - f[0] == pytest.approx(1000 * slope)
    assert slope == 2 * 27 * 16 * (2 * 512 + 64)
    L = 700
    for start, valid in [(0, 256), (512, 188)]:
        want = sum(MODEL.token_flops(CONF, p, p == L - 1)
                   for p in range(start, start + valid))
        assert window.chunk_flops(MODEL, CONF, start, valid, L) == \
            pytest.approx(want, rel=1e-12)


def test_decode_and_expert_costs_by_hand():
    """One expert is 3 x 2048 x 1408 = 8,650,752 parameters; 26 MoE
    layers hold 16 each.  Outside the routed experts a decode step reads
    MLA 27 x 13,765,120 (with its norms), the FFN norms, layer 0's FFN
    67,239,936, 26 x 17,301,504 shared, the 131,072-wide router in float32
    and the head's 209,715,200, and the final norm."""
    expert = 3 * 2048 * 1408
    assert expert == 8_650_752
    dense = (27 * (13_765_120 + 2048) + 3 * 2048 * 10944
             + 26 * 17_301_504 + 102400 * 2048 + 2048)
    hit = lambda t: 1 - (1 - 6 / 64) ** t
    kv = 27 * 576 * 2
    f1, b1 = MODEL.decode_cost(CONF, [1023])
    assert b1 == pytest.approx(dense * 2 + 26 * 131_072 * 4
                               + 26 * 16 * expert * 2 * hit(1) + 1024 * kv)
    f2, b2 = MODEL.decode_cost(CONF, [1023, 99])
    assert b2 - b1 == pytest.approx(26 * 16 * expert * 2 * (hit(2) - hit(1))
                                    + 100 * kv)
    assert f2 - f1 == pytest.approx(MODEL.token_flops(CONF, 99, True))
    # the grouped matmul: 1.5 expected rows per token on held experts
    ef, eb = MODEL.expert_cost(CONF, 24)
    assert ef == pytest.approx(2 * 26 * 24 * 1.5 * expert)
    assert eb == pytest.approx(26 * 16 * expert * 2 * hit(24)
                               + 26 * 24 * 1.5 * 2 * 2048 * 2)
    assert hit(24) == pytest.approx(0.906, abs=1e-3)
    # a 256-row chunk hits every held expert and is bound by its bytes
    cf, cb = MODEL.expert_cost(CONF, 256)
    assert cb / 819e9 > cf / 197e12
    assert cb == pytest.approx(26 * 16 * expert * 2 * hit(256)
                               + 26 * 384 * 2 * 2048 * 2)


def test_cell_loads_with_its_metrics():
    cell = spec.load_cell("deepseek-v2-lite.chat")
    assert cell.chips == 1 and cell.config["model"] == "mla_moe"
    assert {m["name"] for m in cell.end_to_end} == {"ttft_p90_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "queue_wait_p90_ms", "chunk_step_ms", "decode_step_ms.rate",
        "step_mfu.rate", "decode_roofline.rate", "device_idle.rate",
        "expert_roofline"}
    traffic, serve = cell.traffic, cell.config["serve"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] == \
        serve["kv_len"] == cell.config["correct"]["reference_len"]
    assert serve["kv_len"] % serve["block_size"] == 0
    # the minicpm cell is untouched by the new entries
    old = spec.load_cell("minicpm-2b.prefill-heavy")
    assert "expert_roofline" not in {m["name"] for m in old.per_layer}
