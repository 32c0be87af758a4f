"""What the model families' weights and references share: seeded weight
leaves in the serving type, float32 matmuls at ``HIGHEST`` precision, the
float8 control, RMSNorm.  Nothing here imports the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
SERVE_DTYPE = jnp.bfloat16


def seed_key(seed: int):
    """A PRNG key for any whole number up to 2**64."""
    s = int(seed) % 2**64
    key = jax.random.PRNGKey(s & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, (s >> 31) & 0xFFFFFFFF),
                              s >> 63)


def normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        SERVE_DTYPE)


def embed_weights(key, vocab: int, width: int):
    return normal(jax.random.fold_in(key, 0), (vocab, width), 0.02)


def final_norm_weights(key, width: int):
    return normal(jax.random.fold_in(key, 1), (width,), 0.1)


def layer_key(key, layer):
    return jax.random.fold_in(jax.random.fold_in(key, 2), layer)


def stacked_params(layer_weights, n_layers: int, key, vocab: int,
                   width: int, embed_rows: int) -> dict:
    """Every weight in the program's layout: one scan segment of stacked
    layers; embedding rows past the vocabulary are zero."""
    emb = embed_weights(key, vocab, width)
    emb = jnp.concatenate([emb, jnp.zeros((embed_rows - vocab, width),
                                          emb.dtype)])
    layers = jax.vmap(lambda i: layer_weights(layer_key(key, i)))(
        jnp.arange(n_layers))
    return {"embed": emb, "final_norm": final_norm_weights(key, width),
            "seg0": {"c0": layers}}


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def q8(x, axis):
    """float8 e4m3 round trip with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, quant: bool):
    """x @ w in float32; the control rounds both operands to float8."""
    if quant:
        x, w = q8(x, -1), q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def unembed(key, rows, final_w, vocab: int, eps: float, quant: bool):
    """Final norm and tied unembedding: rows [T, d] -> logits [T, vocab]."""
    emb = embed_weights(key, vocab, rows.shape[-1]).astype(jnp.float32)
    return mm(rms_norm(rows, final_w, eps), emb.T, quant)
