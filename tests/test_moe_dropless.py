"""The serving MoE layer: dropless routing over one device's share of the
experts, the gates as DeepSeek-V2 and Mixtral publish them, the expert
load it reports, and the YaRN rope of DeepSeek-V2's latent attention."""

import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models import blocks, lm, mla
from repro.models.config import Yarn

ARCHS = ["deepseek-v2-lite-16b", "mixtral-8x7b"]


def _tiny(arch, **kw):
    """Small widths; 8 routed experts, top 2, one dense layer first for
    DeepSeek."""
    cfg = get(arch).reduced()
    return dataclasses.replace(cfg, n_experts=8, experts_per_token=2, **kw)


def _moe_params(cfg, seed=0):
    return blocks.init_moe(jax.random.PRNGKey(seed), cfg, jnp.float32)


def _x(cfg, B=2, S=24, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.d_model))


@pytest.mark.parametrize("arch", ARCHS)
def test_dropless_equals_the_lossless_dispatch(arch):
    """Holding every expert, the dropless grouped path gives what the
    capacity dispatch gives with room for every pair (capacity T * k, the
    lossless dispatch serving used before): float32, to rounding."""
    cfg = _tiny(arch)
    p, x = _moe_params(cfg), _x(cfg)
    got, aux, load = blocks.moe_layer(cfg, p, x, lossless=True)
    want, aux_want, none = blocks.moe_layer(
        cfg, p, x, capacity_factor=float(cfg.n_experts))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert none is None and float(aux) == pytest.approx(float(aux_want))
    # every pair lands on a held expert: k per token, none elsewhere
    assert load.shape == (2, 24, cfg.n_experts + 1)
    assert np.all(np.asarray(load).sum(-1) == cfg.experts_per_token)
    assert not np.any(np.asarray(load)[..., -1])


@pytest.mark.parametrize("arch", ARCHS)
def test_shares_sum_to_the_whole_layer(arch):
    """Four devices' shares of the experts: their routed parts, with what
    every device computes alike (the residual, the shared experts) counted
    once, add up to the layer that holds every expert."""
    cfg = _tiny(arch)
    p, x = _moe_params(cfg), _x(cfg)
    whole, _, _ = blocks.moe_layer(cfg, p, x, lossless=True)
    common = x
    if "shared" in p:
        sh = p["shared"]
        h = blocks.rms_norm(x, p["ln"], cfg.norm_eps)
        common = x + (jax.nn.silu(h @ sh["w_gate"]) * (h @ sh["w_up"])) \
            @ sh["w_down"]
    total, absent = common, 0
    for c in range(4):
        share = dataclasses.replace(cfg, expert_shard=(c, 4))
        held = share.held_experts
        ps = {**p, **{w: p[w][held.start:held.stop]
                      for w in ("w_gate", "w_up", "w_down")}}
        assert ps["w_gate"].shape[0] == 2 and ps["router"].shape[1] == 8
        out, _, load = blocks.moe_layer(share, ps, x, lossless=True)
        total = total + (out - common)
        absent = absent + np.asarray(load)[..., -1]
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    # each pair is held by exactly one of the four shares
    assert np.all(absent == 3 * cfg.experts_per_token)


def test_held_share_is_the_experts_initialised():
    cfg = _tiny("deepseek-v2-lite-16b", expert_shard=(1, 4))
    p = _moe_params(cfg)
    assert list(cfg.held_experts) == [2, 3]
    assert p["w_gate"].shape[0] == 2 and p["router"].shape[1] == 8
    size = lambda c: sum(a.size for a in jax.tree.leaves(
        lm.init_params(c, jax.random.PRNGKey(0), jnp.float32)))
    whole = dataclasses.replace(cfg, expert_shard=(0, 1))
    dropped = (cfg.n_layers - cfg.first_k_dense) * 6 * 3 * cfg.d_model \
        * cfg.d_ff_expert
    assert size(whole) - size(cfg) == dropped
    assert whole.param_count() - cfg.param_count() == dropped
    # a compiled plan stores its config as JSON: the share and the YaRN
    # parameters come back as the same config
    from repro.core.plan import _cfg_from_json, _cfg_to_json
    assert _cfg_from_json(json.loads(json.dumps(_cfg_to_json(cfg)))) == cfg
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, expert_shard=(0, 3))
    with pytest.raises(ValueError):
        blocks.moe_layer(cfg, p, _x(cfg))          # training needs all


@pytest.mark.parametrize("norm", [True, False])
def test_norm_topk_prob(norm):
    """Mixtral renormalises the top-k gates; DeepSeek-V2 keeps the softmax
    probabilities of the chosen experts."""
    cfg = _tiny("mixtral-8x7b", norm_topk_prob=norm)
    p = _moe_params(cfg)
    flat = _x(cfg).reshape(-1, cfg.d_model)
    probs, gates, idx = blocks._route(cfg, p, flat)
    picked = jnp.take_along_axis(probs, idx, -1)
    if norm:
        np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)
        np.testing.assert_allclose(gates, picked / picked.sum(-1,
                                                             keepdims=True),
                                   rtol=1e-6)
    else:
        np.testing.assert_array_equal(gates, picked)
        assert float(gates.sum(-1).max()) < 1.0
    assert get("deepseek-v2-lite-16b").norm_topk_prob is False
    assert get("mixtral-8x7b").norm_topk_prob is True


def test_serve_step_load_counts_real_rows_only():
    """The decode program's load counts only the active lanes' pairs."""
    from repro.serve.engine import ContinuousEngine
    cfg = _tiny("deepseek-v2-lite-16b", expert_shard=(0, 2))
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ContinuousEngine(cfg, params, kv_len=32, n_slots=3, paged=True,
                           prefill_chunk=8)
    eng.submit(list(range(1, 12)), 4, rid="a")
    eng.run()
    steps = list(eng.telemetry.steps)
    n_moe = cfg.n_layers - cfg.first_k_dense
    k = cfg.experts_per_token
    # 11 prompt rows (two chunks) and 3 decoded tokens, k pairs each
    pairs = sum(sum(s.experts) for s in steps if s.experts is not None)
    assert pairs == (11 + 3) * k * n_moe
    assert eng.telemetry.expert_load() is not None
    assert sum(eng.telemetry.expert_load()) == pairs
    assert 0.0 <= eng.telemetry.absent_share() <= 1.0
    assert eng.telemetry.expert_imbalance() >= 1.0


# -- YaRN ----------------------------------------------------------------------

DS_YARN = Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
               beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def test_yarn_frequencies_follow_the_published_formulas():
    """DeepseekV2YarnRotaryEmbedding at DeepSeek-V2-Lite's numbers: the
    correction range is dims 10 to 23 of the 32 pairs."""
    dim, base = 64, 10_000.0
    i = np.arange(0, dim, 2) / dim
    extra = 1.0 / base ** i
    inter = extra / 40.0
    corr = lambda rot: dim * math.log(4096 / (rot * 2 * math.pi)) \
        / (2 * math.log(base))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = inter * (1 - mask) + extra * mask
    got = blocks.rope_freqs(dim, base, DS_YARN)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # below the range the pairs keep their frequency, above it they are
    # interpolated by the factor
    np.testing.assert_allclose(got[:10], extra[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], extra[23:] / 40.0, rtol=1e-6)


def test_yarn_softmax_scale_and_unscaled_rope():
    cfg = get("deepseek-v2-lite-16b")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert mla.softmax_scale(dataclasses.replace(cfg, rope_yarn=None)) == \
        pytest.approx(192 ** -0.5)
    # cos and sin are scaled by mscale / mscale_all_dim: 1 here
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 64))
    pos = jnp.arange(5)
    norms = jnp.linalg.norm(blocks.apply_rope(x, pos, 1e4, DS_YARN), axis=-1)
    np.testing.assert_allclose(norms, jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # without scaling the table is the plain one, as MiniCPM uses it
    plain = 1.0 / 10_000.0 ** (jnp.arange(0, 64, 2, dtype=jnp.float32) / 64)
    np.testing.assert_array_equal(blocks.rope_freqs(64, 10_000.0), plain)
    assert get("minicpm-2b").rope_yarn is None
