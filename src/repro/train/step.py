"""Train-step factory: loss + grad + AdamW update, pjit-ready.

The returned ``train_step(params, opt_state, batch, step)`` is a pure
function: the launcher jits it with in/out shardings from
``core.placement.ShardingRules`` and, on the multi-pod mesh, an int8
error-feedback compressed cross-pod gradient reduction can be enabled
(``grad_compression="int8_ef"``; see ``optim.compression``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig
from repro.optim import adamw


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross entropy, vocab-sharding friendly.

    No gather over the (possibly model-sharded) vocab dim: the gold logit is
    extracted with a fused iota-compare contraction and logsumexp reduces the
    sharded dim locally + a small all-reduce. Avoids ever materializing an
    unsharded [B, S, V] f32 tensor (62 GiB/device for command-r train_4k).
    """
    V = logits.shape[-1]
    lmax = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = (logits - lmax).astype(jnp.float32)
    logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + lmax[..., 0].astype(jnp.float32)
    onehot = (labels[..., None] == jnp.arange(V, dtype=labels.dtype))
    gold = jnp.sum(jnp.where(onehot, shifted, 0.0), axis=-1) + \
        lmax[..., 0].astype(jnp.float32)
    return jnp.mean(logz - gold)


@dataclass(frozen=True)
class TrainStepConfig:
    impl: str = "chunked"
    n_groups: int = 1
    capacity_factor: float = 1.25
    grad_accum: int = 1
    unroll: bool = False   # unroll layer scans (dry-run: exact HLO flop counts)
    adamw: AdamWConfig = field(default_factory=AdamWConfig)


def make_train_step(cfg: ModelConfig, lr_fn: Callable,
                    tcfg: TrainStepConfig = TrainStepConfig(),
                    shard_fn=None, grad_constraint=None):
    F = cfg.frontend_tokens if (cfg.frontend and not cfg.n_enc_layers) else 0

    def loss_fn(params, batch):
        logits, _, aux = lm.forward(
            cfg, params, batch["tokens"],
            frontend_emb=batch.get("frontend_emb"),
            mode="train", impl=tcfg.impl, n_groups=tcfg.n_groups,
            capacity_factor=tcfg.capacity_factor, shard_fn=shard_fn,
            unroll=tcfg.unroll)
        lg = logits[:, F:] if F else logits
        ce = cross_entropy(lg, batch["labels"])
        return ce + aux.loss, {"ce": ce, "aux": aux.loss}

    def train_step(params, opt_state, batch, step):
        if tcfg.grad_accum > 1:
            # split batch into microbatches along the batch dim and accumulate
            def micro(b):
                return jax.value_and_grad(loss_fn, has_aux=True)(params, b)

            mb = jax.tree.map(
                lambda x: x.reshape((tcfg.grad_accum,
                                     x.shape[0] // tcfg.grad_accum) + x.shape[1:]),
                batch)

            def body(acc, b):
                (l, m), g = micro(b)
                if grad_constraint is not None:
                    # constrain per-microbatch grads to the FSDP layout so
                    # SPMD reduce-scatters each microbatch (ZeRO-2) instead
                    # of all-reducing f32 tuples (§Perf it.7b)
                    g = grad_constraint(g)
                acc_g, acc_l = acc
                return (jax.tree.map(jnp.add, acc_g, g), acc_l + l), m

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), metrics = jax.lax.scan(
                body, (zero, 0.0), mb,
                unroll=tcfg.grad_accum if tcfg.unroll else 1)
            # bf16 cross-data gradient reduction (f32 accumulation stays
            # local): halves the dominant wire term on 35B train cells
            grads = jax.tree.map(
                lambda g, p: (g / tcfg.grad_accum).astype(p.dtype),
                grads, params)
            loss = loss / tcfg.grad_accum
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)

        if grad_constraint is not None:
            # pin grads to the (FSDP) param sharding so SPMD emits
            # reduce-scatter instead of all-reduce+slice (§Perf it.7)
            grads = grad_constraint(grads)
        lr = lr_fn(step)
        params, opt_state, om = adamw.update(params, grads, opt_state, lr,
                                             tcfg.adamw)
        return params, opt_state, {"loss": loss, **metrics, **om}

    return train_step, loss_fn
