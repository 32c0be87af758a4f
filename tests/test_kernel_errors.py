"""Shapes a kernel cannot take raise ``ValueError``: the jnp oracles are
never substituted in silence (callers pick ``ref.reference`` explicitly)."""

import jax
import jax.numpy as jnp
import pytest


def _flash(Sq, Skv, H, KV, **kw):
    from repro.kernels.flash_attention import flash_attention
    q = jnp.zeros((1, Sq, H, 16))
    k = jnp.zeros((1, Skv, KV, 16))
    return lambda: flash_attention(q, k, k, q_positions=jnp.arange(Sq),
                                   k_positions=jnp.arange(Skv),
                                   interpret=True, **kw)


def _paged(H, KV):
    from repro.kernels.paged_attention import paged_attention
    q = jnp.zeros((2, H, 16))
    pages = jnp.zeros((5, 8, KV, 16))
    tables = jnp.zeros((2, 2), jnp.int32)
    return lambda: paged_attention(q, pages, pages, tables,
                                   jnp.array([3, 9], jnp.int32),
                                   interpret=True)


def _ssd_carried_state():
    from repro.configs import get
    from repro.models import lm, ssm
    cfg = get("mamba2-370m").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = lm.init_cache(cfg, 1, 16, jnp.float32)
    layer = lambda t: t["seg0"]["c0"]["ssd"]
    p = jax.tree.map(lambda a: a[0], layer(params))
    c = jax.tree.map(lambda a: a[0], layer(cache))
    x = jnp.zeros((1, 8, cfg.d_model))
    return lambda: ssm.ssd_layer(cfg, p, x, cache=c, impl="pallas")


CASES = {
    "flash_q_rows_not_tiling": lambda: _flash(96, 128, 2, 2, block_q=64),
    "flash_kv_rows_not_tiling": lambda: _flash(128, 96, 2, 2, block_k=64),
    "flash_ragged_head_groups": lambda: _flash(128, 128, 3, 2),
    "paged_ragged_head_groups": lambda: _paged(3, 2),
    "ssd_pallas_carried_state": _ssd_carried_state,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_untileable_shape_raises(name):
    call = CASES[name]()
    with pytest.raises(ValueError):
        call()
