"""DeepSeek-V2 decoder (multi-head latent attention, a dense FFN in the
first layers, then sparse-expert FFNs) over one chip's share of the
routed experts: weights from the seed, the plain float32 reference, and
the work one token needs.

The reference imports nothing of the program.  It follows the published
equations (arXiv:2405.04434 and the configuration file), in float32 with
every matmul at ``HIGHEST`` precision; ``quant=True`` is the control,
every projection in float8:

* Attention, not absorbed: the query projection (no compression, as
  ``q_lora_rank`` is null) splits per head into ``qk_nope_head_dim`` and
  ``qk_rope_head_dim`` columns; the key-value down-projection gives the
  ``kv_lora_rank`` latent, RMS-normed, and one rope key shared by every
  head; ``k_nope`` and ``v`` are the latent's up-projections.  Rope is
  YaRN-scaled as DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` computes
  it (frequencies interpolated by ``factor`` below the correction range
  of ``beta_fast``/``beta_slow`` rotations over the original 4,096
  positions, cos/sin scaled by mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)), and the causal softmax runs at the published scale,
  ``q_head_dim ** -0.5`` times mscale(factor, mscale_all_dim) squared.
* FFN: a dense SwiGLU in the first ``first_k_dense_replace`` layers
  (``correct.Reference`` passes the layer as a traced value, so the layer
  branches with ``lax.cond``); then the published MoE: a softmax over all
  routed experts' float32 scores, greedy top ``num_experts_per_tok``,
  the gates renormalised only under ``norm_topk_prob``, times
  ``routed_scaling_factor``; the shared experts, one SwiGLU of
  ``n_shared_experts`` x ``moe_intermediate_size``, added in full.
* Head: final RMSNorm and an untied unembedding of its own.

Departures from the paper, each in the program as well: this chip holds
``n_routed_experts`` of the published ``deployment.n_routed_experts``
(``deployment.this_chip``'s share), and what the experts held elsewhere
would add is left out; rope rotates the two halves of the rope columns
(the published weights interleave each pair: the same map up to a fixed
permutation of those columns); RMSNorm scales by ``1 + w``.

The routed experts are computed densely: each held expert runs over every
row, and a row keeps its output at the gate of its token's pair on that
expert, at 0 where the token did not choose it.  Work counts
(``token_flops``, ``decode_cost``, ``expert_cost``) take each token's
pairs on held experts at their expected number, ``k x held / published``
per MoE layer, and a held expert as read when at least one of ``T``
tokens picks it, with probability ``1 - (1 - k / published) ** T``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.refmath import (HIGHEST, SERVE_DTYPE, embed_weights, f32,
                           final_norm_weights, layer_key, mm, normal, q8,
                           rms_norm)

def dims(conf: dict) -> dict:
    dep = conf["deployment"]
    held = conf["n_routed_experts"]
    if held * dep["expert_parallel"] != dep["n_routed_experts"]:
        raise ValueError("n_routed_experts x expert_parallel must be the "
                         "published expert count")
    return {"L": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "H": conf["num_attention_heads"], "V": conf["vocab_size"],
            "r": conf["kv_lora_rank"], "nope": conf["qk_nope_head_dim"],
            "rope": conf["qk_rope_head_dim"], "vd": conf["v_head_dim"],
            "ff": conf["intermediate_size"],
            "fe": conf["moe_intermediate_size"],
            "E": dep["n_routed_experts"], "Eh": held,
            "lo": dep["this_chip"] * held,
            "k": conf["num_experts_per_tok"],
            "shared": conf["n_shared_experts"],
            "dense": conf["first_k_dense_replace"]}


def program_kwargs(conf: dict) -> dict:
    """The program's ``ModelConfig`` fields for this file."""
    m, rs, dep = dims(conf), conf["rope_scaling"], conf["deployment"]
    return dict(name=conf["name"], family="moe", n_layers=m["L"],
                d_model=m["d"], n_heads=m["H"],
                n_kv_heads=conf["num_key_value_heads"], head_dim=m["vd"],
                d_ff=m["ff"], vocab_size=m["V"],
                layer_cycle=(("mla", "moe"),), first_k_dense=m["dense"],
                n_experts=m["E"], experts_per_token=m["k"],
                n_shared_experts=m["shared"], d_ff_expert=m["fe"],
                norm_topk_prob=conf["norm_topk_prob"],
                expert_shard=(dep["this_chip"], dep["expert_parallel"]),
                q_lora_rank=conf["q_lora_rank"] or 0, kv_lora_rank=m["r"],
                qk_rope_dim=m["rope"], qk_nope_dim=m["nope"],
                v_head_dim=m["vd"], ffn_act=conf["hidden_act"],
                rope_theta=conf["rope_theta"],
                rope_yarn=(rs["factor"],
                           rs["original_max_position_embeddings"],
                           rs["beta_fast"], rs["beta_slow"], rs["mscale"],
                           rs["mscale_all_dim"]),
                tie_embeddings=conf["tie_word_embeddings"],
                norm_eps=conf["rms_norm_eps"])


# -- weights ------------------------------------------------------------------

def _swiglu_weights(keys, d: int, ff: int, lead=()) -> dict:
    return {"w_gate": normal(keys[0], lead + (d, ff), d ** -0.5),
            "w_up": normal(keys[1], lead + (d, ff), d ** -0.5),
            "w_down": normal(keys[2], lead + (ff, d), ff ** -0.5)}


def layer_weights(conf: dict, key, moe: bool) -> dict:
    """One layer's weights in the program's layout, in the serving type
    (the router in float32)."""
    m = dims(conf)
    d, H, r = m["d"], m["H"], m["r"]
    k = [jax.random.fold_in(key, i) for i in range(16)]
    out = {"mla": {
        "ln": normal(k[0], (d,), 0.1),
        "wq": normal(k[1], (d, H * (m["nope"] + m["rope"])), d ** -0.5),
        "wkv_down": normal(k[2], (d, r + m["rope"]), d ** -0.5),
        "kv_ln": normal(k[3], (r,), 0.1),
        "wk_up": normal(k[4], (r, H * m["nope"]), r ** -0.5),
        "wv_up": normal(k[5], (r, H * m["vd"]), r ** -0.5),
        "wo": normal(k[6], (H * m["vd"], d), (H * m["vd"]) ** -0.5)}}
    if not moe:
        out["ffn"] = {"ln": normal(k[7], (d,), 0.1),
                      **_swiglu_weights(k[8:11], d, m["ff"])}
        return out
    out["moe"] = {
        "ln": normal(k[7], (d,), 0.1),
        "router": jax.random.normal(k[8], (d, m["E"]), jnp.float32)
        * d ** -0.5,
        **_swiglu_weights(k[9:12], d, m["fe"], (m["Eh"],)),
        "shared": _swiglu_weights(k[12:15], d, m["shared"] * m["fe"])}
    return out


def unembed_weights(key, width: int, vocab: int):
    return normal(jax.random.fold_in(key, 3), (width, vocab), width ** -0.5)


def program_params(conf: dict, key, embed_rows: int) -> dict:
    """Every weight in the program's layout: the dense layers, then the
    MoE layers, each a scan segment, made one layer at a time."""
    m = dims(conf)
    pad = embed_rows - m["V"]
    emb = embed_weights(key, m["V"], m["d"])
    head = unembed_weights(key, m["d"], m["V"])

    def stack(first: int, last: int, moe: bool) -> dict:
        return lax.map(lambda i: layer_weights(conf, layer_key(key, i), moe),
                       jnp.arange(first, last))

    return {"embed": jnp.pad(emb, ((0, pad), (0, 0))),
            "final_norm": final_norm_weights(key, m["d"]),
            "unembed": jnp.pad(head, ((0, 0), (0, pad))),
            "seg0": {"c0": stack(0, m["dense"], False)},
            "seg1": {"c0": stack(m["dense"], m["L"], True)}}


# -- reference -----------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(conf: dict):
    """The published YaRN inverse frequencies of the rope columns."""
    rs, dim, base = (conf["rope_scaling"], conf["qk_rope_head_dim"],
                     conf["rope_theta"])
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / rs["factor"]

    def correction(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def softmax_scale(conf: dict) -> float:
    rs = conf["rope_scaling"]
    q_head = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
    return (q_head ** -0.5
            * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2)


def rope(conf: dict, x):
    """x: [S, heads, rope dims] at positions 0..S-1; the halves rotate."""
    rs = conf["rope_scaling"]
    S, _, dim = x.shape
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * yarn_inv_freq(conf)
    m = (yarn_mscale(rs["factor"], rs["mscale"])
         / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    c, s = (jnp.cos(ang) * m)[:, None], (jnp.sin(ang) * m)[:, None]
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def _attention(conf: dict, a: dict, x, quant: bool):
    """Non-absorbed MLA of one sequence x [S, d]."""
    m = dims(conf)
    S, H, nope = x.shape[0], m["H"], m["nope"]
    eps = conf["rms_norm_eps"]
    n = rms_norm(x, a["ln"], eps)
    q = mm(n, a["wq"], quant).reshape(S, H, nope + m["rope"])
    kv = mm(n, a["wkv_down"], quant)
    latent = rms_norm(kv[:, : m["r"]], a["kv_ln"], eps)
    k_rope = rope(conf, kv[:, None, m["r"]:])                 # [S, 1, rope]
    k_nope = mm(latent, a["wk_up"], quant).reshape(S, H, nope)
    v = mm(latent, a["wv_up"], quant).reshape(S, H, m["vd"])
    q = jnp.concatenate([q[..., :nope], rope(conf, q[..., nope:])], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (S, H, m["rope"]))],
                        -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * softmax_scale(conf)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(S, -1)
    return mm(o, a["wo"], quant)


def _swiglu(w: dict, n, quant: bool):
    g = jax.nn.silu(mm(n, w["w_gate"], quant)) * mm(n, w["w_up"], quant)
    return mm(g, w["w_down"], quant)


def _routed(conf: dict, w: dict, n, quant: bool):
    """The held experts' part of the MoE for one sequence n [S, d]: every
    held expert over every row, each row weighted by its token's gate
    where the token chose that expert and by 0 where it did not."""
    m = dims(conf)
    router = q8(w["router"], 0) if quant else w["router"]
    probs = jax.nn.softmax(jnp.matmul(q8(n, -1) if quant else n, router,
                                      precision=HIGHEST), -1)
    gates, experts = lax.top_k(probs, m["k"])                   # [S, k]
    if conf["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * conf["routed_scaling_factor"]

    def expert(e, out):
        we = {name: w[name][e] for name in ("w_gate", "w_up", "w_down")}
        gate = jnp.sum(jnp.where(experts == m["lo"] + e, gates, 0.0), -1)
        return out + gate[:, None] * _swiglu(we, n, quant)

    return lax.fori_loop(0, m["Eh"], expert, jnp.zeros_like(n))


def ref_embed(conf: dict, key, tokens):
    """tokens [R, S] -> float32 hidden rows [R, S, d]."""
    m = dims(conf)
    return embed_weights(key, m["V"], m["d"]).astype(jnp.float32)[tokens]


def ref_layer(conf: dict, key, layer, h, quant: bool):
    """One block over h [R, S, d], one sequence at a time."""
    m = dims(conf)
    eps = conf["rms_norm_eps"]

    def dense(h):
        w = f32(layer_weights(conf, layer_key(key, layer), False))

        def one(x):
            x = x + _attention(conf, w["mla"], x, quant)
            f = w["ffn"]
            return x + _swiglu(f, rms_norm(x, f["ln"], eps), quant)

        return lax.map(one, h)

    def moe(h):
        w = f32(layer_weights(conf, layer_key(key, layer), True))

        def one(x):
            x = x + _attention(conf, w["mla"], x, quant)
            f = w["moe"]
            n = rms_norm(x, f["ln"], eps)
            return x + _routed(conf, f, n, quant) + _swiglu(f["shared"], n,
                                                            quant)

        return lax.map(one, h)

    return lax.cond(layer < m["dense"], dense, moe, h)


def ref_logits(conf: dict, key, rows, quant: bool):
    """Hidden rows [T, d] after the last layer -> logits [T, V]."""
    m = dims(conf)
    fw = final_norm_weights(key, m["d"]).astype(jnp.float32)
    head = unembed_weights(key, m["d"], m["V"]).astype(jnp.float32)
    return mm(rms_norm(rows, fw, conf["rms_norm_eps"]), head, quant)


# -- work ----------------------------------------------------------------------

def _sizes(conf: dict) -> dict:
    """Parameters by part, as the program holds them."""
    m = dims(conf)
    d, H, r = m["d"], m["H"], m["r"]
    mla = (d * H * (m["nope"] + m["rope"]) + d * (r + m["rope"])
           + r * H * (m["nope"] + m["vd"]) + H * m["vd"] * d)
    return {"mla": mla, "mla_norms": d + r, "dense_ffn": 3 * d * m["ff"],
            "expert": 3 * d * m["fe"], "shared": 3 * d * m["shared"] * m["fe"],
            "router": d * m["E"], "embed": m["V"] * d}


def _moe_layers(conf: dict) -> int:
    m = dims(conf)
    return m["L"] - m["dense"]


def param_count(conf: dict) -> int:
    """Every parameter the program holds: the held experts only."""
    m, s = dims(conf), _sizes(conf)
    d = m["d"]
    per_layer = s["mla"] + s["mla_norms"] + d
    dense = m["dense"] * (per_layer + s["dense_ffn"])
    moe = _moe_layers(conf) * (per_layer + s["router"] + s["shared"]
                               + m["Eh"] * s["expert"])
    return dense + moe + 2 * s["embed"] + d


def kv_bytes_per_token(conf: dict) -> int:
    m = dims(conf)
    return m["L"] * (m["r"] + m["rope"]) * jnp.dtype(SERVE_DTYPE).itemsize


def _pairs_held(conf: dict) -> float:
    """Expected (token, slot) pairs per token on held experts, per MoE
    layer."""
    m = dims(conf)
    return m["k"] * m["Eh"] / m["E"]


def token_flops(conf: dict, pos: int, logits: bool) -> float:
    """FLOPs the token at position ``pos`` needs: the attention
    projections, latent attention over its ``pos + 1`` resident positions
    (absorbed: scores against latent and rope key, values in the latent),
    the dense FFN or the router, shared experts and its expected pairs on
    held experts, and the unembedding where its logits are read."""
    m, s = dims(conf), _sizes(conf)
    n_moe = _moe_layers(conf)
    matmul = (m["L"] * s["mla"] + m["dense"] * s["dense_ffn"]
              + n_moe * (s["router"] + s["shared"]
                         + _pairs_held(conf) * s["expert"]))
    attn = 2 * m["L"] * m["H"] * (2 * m["r"] + m["rope"]) * (pos + 1)
    return 2.0 * matmul + attn + (2.0 * s["embed"] if logits else 0.0)


def hit_fraction(conf: dict, tokens: int) -> float:
    """Expected share of the held experts that at least one of ``tokens``
    tokens routes to."""
    m = dims(conf)
    return 1.0 - (1.0 - m["k"] / m["E"]) ** tokens


def expert_cost(conf: dict, tokens: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) the grouped expert matmuls need over the MoE
    layers for one program's ``tokens`` tokens: the expected rows routed
    to held experts through gate, up and down projections; the weights of
    each held expert that is hit read once, those rows read and the
    outputs written once."""
    m, s = dims(conf), _sizes(conf)
    n_moe = _moe_layers(conf)
    item = jnp.dtype(SERVE_DTYPE).itemsize
    rows = tokens * _pairs_held(conf)
    flops = 2.0 * n_moe * rows * s["expert"]
    weights = n_moe * m["Eh"] * s["expert"] * item * hit_fraction(conf,
                                                                  tokens)
    return flops, float(weights + n_moe * rows * 2 * m["d"] * item)


def decode_cost(conf: dict, positions) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one decode step needs for active lanes whose new
    tokens sit at ``positions``: every weight outside the routed experts
    read once (the embedding gives only its rows), the held experts at
    their expected hit fraction, each lane's resident latents read once
    and its new latent row written once."""
    m, s = dims(conf), _sizes(conf)
    item = jnp.dtype(SERVE_DTYPE).itemsize
    n_moe = _moe_layers(conf)
    dense = (param_count(conf) - s["embed"] - n_moe * m["Eh"] * s["expert"]
             - n_moe * s["router"]) * item + n_moe * s["router"] * 4
    experts = (n_moe * m["Eh"] * s["expert"] * item
               * hit_fraction(conf, len(positions)))
    kv = kv_bytes_per_token(conf)
    flops = sum(token_flops(conf, p, True) for p in positions)
    return flops, float(dense + experts
                        + sum(kv * (p + 1) for p in positions))
