"""Deliverable (f): per-arch REDUCED-config smoke tests — one forward and one
train step on CPU, asserting output shapes and no NaNs."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get
from repro.models import lm
from repro.optim import init_state, warmup_cosine
from repro.train import make_train_step, TrainStepConfig


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key, jnp.float32)
    B, S = 2, 32
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    fe = (jax.random.normal(key, (B, cfg.frontend_tokens, cfg.frontend_dim),
                            jnp.float32) if cfg.frontend else None)
    logits, cache, aux = lm.forward(cfg, params, tokens, frontend_emb=fe,
                                    mode="train", remat=False)
    F = cfg.frontend_tokens if (cfg.frontend and not cfg.n_enc_layers) else 0
    assert logits.shape == (B, S + F, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux.loss))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_runs_and_is_finite(arch):
    cfg = get(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key, jnp.float32)
    opt = init_state(params)
    step_fn, _ = make_train_step(cfg, warmup_cosine(1e-3, 2, 100),
                                 TrainStepConfig())
    B, S = 2, 32
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    if cfg.frontend:
        batch["frontend_emb"] = jax.random.normal(
            key, (B, cfg.frontend_tokens, cfg.frontend_dim), jnp.float32)
    params2, opt2, m = jax.jit(step_fn)(params, opt, batch, jnp.asarray(1))
    assert bool(jnp.isfinite(m["loss"]))
    assert bool(jnp.isfinite(m["grad_norm"]))
    # params actually changed
    delta = jax.tree.reduce(
        lambda acc, x: acc + float(jnp.sum(jnp.abs(x.astype(jnp.float32)))),
        jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                     params, params2), 0.0)
    assert delta > 0.0
