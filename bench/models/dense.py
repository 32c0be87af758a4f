"""Dense pre-norm decoder (MiniCPM as this program runs it): weights from the
seed, the plain float32 reference, and the work one token needs.

The reference imports nothing of the program.  It follows the equations
the configuration file states: pre-norm blocks with RMSNorm scaled by
``1 + w``, rotary embeddings on the two halves of each head, causal
softmax attention, a SwiGLU MLP, a final norm and the tied embedding as
the unembedding.  Float32, every matmul at ``HIGHEST`` precision;
``quant=True`` is the control, every projection in float8.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.refmath import (HIGHEST, SERVE_DTYPE, embed_weights, f32,
                           final_norm_weights, layer_key, mm, normal,
                           rms_norm, stacked_params, unembed)


def dims(conf: dict) -> dict:
    return {"L": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "KV": conf["num_key_value_heads"], "hd": conf["head_dim"],
            "ff": conf["intermediate_size"], "V": conf["vocab_size"]}


def program_kwargs(conf: dict) -> dict:
    """The program's ``ModelConfig`` fields for this file."""
    m = dims(conf)
    return dict(name=conf["name"], family="dense", n_layers=m["L"],
                d_model=m["d"], n_heads=m["H"], n_kv_heads=m["KV"],
                head_dim=m["hd"], d_ff=m["ff"], vocab_size=m["V"],
                layer_cycle=(("global", "dense"),),
                ffn_act=conf["hidden_act"], rope_theta=conf["rope_theta"],
                tie_embeddings=conf["tie_word_embeddings"],
                norm_eps=conf["rms_norm_eps"])


# -- weights ------------------------------------------------------------------

def layer_weights(conf: dict, key) -> dict:
    """One layer's weights in the program's layout, in the serving type."""
    m = dims(conf)
    d, q, kv, ff = m["d"], m["H"] * m["hd"], m["KV"] * m["hd"], m["ff"]
    k = [jax.random.fold_in(key, i) for i in range(9)]
    return {
        "attn": {"ln": normal(k[0], (d,), 0.1),
                 "wq": normal(k[1], (d, q), d ** -0.5),
                 "wk": normal(k[2], (d, kv), d ** -0.5),
                 "wv": normal(k[3], (d, kv), d ** -0.5),
                 "wo": normal(k[4], (q, d), q ** -0.5)},
        "ffn": {"ln": normal(k[5], (d,), 0.1),
                "w_gate": normal(k[6], (d, ff), d ** -0.5),
                "w_up": normal(k[7], (d, ff), d ** -0.5),
                "w_down": normal(k[8], (ff, d), ff ** -0.5)},
    }


def program_params(conf: dict, key, embed_rows: int) -> dict:
    m = dims(conf)
    return stacked_params(lambda k: layer_weights(conf, k), m["L"], key,
                          m["V"], m["d"], embed_rows)


# -- reference -----------------------------------------------------------------

def rope(x, theta):
    """x: [S, heads, hd] at positions 0..S-1; the two halves rotate."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv     # [S, hd/2]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def _attend(q, k, v):
    """Causal softmax attention of one sequence: q [S, H, hd], k/v [S, KV,
    hd]."""
    S, H, hd = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


def ref_embed(conf: dict, key, tokens):
    """tokens [R, S] -> float32 hidden rows [R, S, d]."""
    m = dims(conf)
    return embed_weights(key, m["V"], m["d"]).astype(jnp.float32)[tokens]


def ref_layer(conf: dict, key, layer, h, quant: bool):
    """One block over h [R, S, d], one sequence at a time."""
    m = dims(conf)
    w = f32(layer_weights(conf, layer_key(key, layer)))
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    a, f = w["attn"], w["ffn"]

    def one(x):                                       # x: [S, d]
        S = x.shape[0]
        n = rms_norm(x, a["ln"], eps)
        q = rope(mm(n, a["wq"], quant).reshape(S, m["H"], m["hd"]), theta)
        k = rope(mm(n, a["wk"], quant).reshape(S, m["KV"], m["hd"]), theta)
        v = mm(n, a["wv"], quant).reshape(S, m["KV"], m["hd"])
        x = x + mm(_attend(q, k, v).reshape(S, -1), a["wo"], quant)
        n = rms_norm(x, f["ln"], eps)
        g = jax.nn.silu(mm(n, f["w_gate"], quant)) * mm(n, f["w_up"], quant)
        return x + mm(g, f["w_down"], quant)

    return lax.map(one, h)


def ref_logits(conf: dict, key, rows, quant: bool):
    """Hidden rows [T, d] after the last layer -> logits [T, V]."""
    m = dims(conf)
    fw = final_norm_weights(key, m["d"]).astype(jnp.float32)
    return unembed(key, rows, fw, m["V"], conf["rms_norm_eps"], quant)


# -- work ----------------------------------------------------------------------

def param_count(conf: dict) -> int:
    m = dims(conf)
    per_layer = (2 * m["d"] + m["d"] * (m["H"] + 2 * m["KV"]) * m["hd"]
                 + m["H"] * m["hd"] * m["d"] + 3 * m["d"] * m["ff"])
    return m["L"] * per_layer + m["V"] * m["d"] + m["d"]


def kv_bytes_per_token(conf: dict) -> int:
    m = dims(conf)
    return m["L"] * 2 * m["KV"] * m["hd"] * jnp.dtype(SERVE_DTYPE).itemsize


def token_flops(conf: dict, pos: int, logits: bool) -> float:
    """FLOPs the token at position ``pos`` needs: every layer's matmuls,
    attention over its ``pos + 1`` resident positions, and the
    unembedding where its logits are read."""
    m = dims(conf)
    layer_params = param_count(conf) - m["V"] * m["d"] - m["d"]
    attn = 4 * m["L"] * m["H"] * m["hd"] * (pos + 1)
    return 2.0 * layer_params + attn + (2.0 * m["V"] * m["d"] if logits
                                        else 0.0)


def decode_cost(conf: dict, positions) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one decode step needs for active lanes whose new
    tokens sit at ``positions``: the weights read once, each lane's
    resident K/V read once and its new K/V row written once."""
    weight_bytes = param_count(conf) * jnp.dtype(SERVE_DTYPE).itemsize
    kv = kv_bytes_per_token(conf)
    flops = sum(token_flops(conf, p, True) for p in positions)
    return flops, float(weight_bytes + sum(kv * (p + 1) for p in positions))
