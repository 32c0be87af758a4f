"""Pipeline-parallel training — the FAITHFUL realization of the paper's
device placement on a TPU mesh.

The partitioner's stage assignment (Plan.layer_to_stage, convex mode) maps
layers onto the ``model`` mesh axis; activations cross stage boundaries via
``jax.lax.ppermute`` — the wire bytes are exactly the cut edges the paper's
objective minimizes. Schedule: GPipe with M microbatches over T = M + S - 1
ticks; at tick t, stage s computes microbatch (t - s), bubbles masked out.
Backward flows through the reversed ppermutes (shard_map autodiff), which
reproduces the GPipe backward schedule.

Scope: uniform-cycle decoder-only archs with n_layers % n_stages == 0
(mixtral-8x7b and phi-3-vision-4.2b hit this on the 16-wide production
mesh). Heterogeneous stage sizes fall back to the tensor backend — recorded
in DESIGN.md §2.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import blocks
from repro.models.config import LayerSpec, ModelConfig
from repro.optim import adamw, AdamWConfig


def _layer_fwd(cfg: ModelConfig, spec: LayerSpec, p: dict, x, positions):
    """One uniform layer (attention/local attention + dense/moe FFN)."""
    x, _ = blocks.attn_layer(cfg, p["attn"], x,
                             local=(spec.mixer == "local"),
                             positions=positions, impl="chunked")
    if spec.ffn == "dense":
        x = blocks.ffn_layer(cfg, p["ffn"], x)
    elif spec.ffn == "moe":
        x, _, _ = blocks.moe_layer(cfg, p["moe"], x, n_groups=1)
    return x


def make_pipeline_train_step(cfg: ModelConfig, mesh: Mesh, *,
                             n_microbatches: int = 8,
                             lr_fn=None, adamw_cfg: AdamWConfig = AdamWConfig(),
                             stage_axis: str = "model",
                             data_axis: str = "data"):
    """Returns (train_step, param_specs, batch_spec) for jit-with-shardings.

    Parameters are the standard ``lm.init_params`` tree; per-segment stacked
    layer dims are split across stages (leading dim over ``stage_axis``).
    """
    segs = cfg.segments()
    assert len(segs) == 1 and len(segs[0].cycle) == 1, \
        "pipeline backend: uniform-cycle archs (see DESIGN.md)"
    spec = segs[0].cycle[0]
    n_layers = cfg.n_layers
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_stages = axes[stage_axis]
    assert n_layers % n_stages == 0, (n_layers, n_stages)
    M = n_microbatches

    # -- shard_map specs -------------------------------------------------------
    def param_spec(path, leaf):
        names = [str(p.key) if hasattr(p, "key") else str(p.idx) for p in path]
        if names[0].startswith("seg"):
            return P(stage_axis, *([None] * (leaf.ndim - 1)))
        return P()  # embed/unembed/final_norm replicated across stages

    batch_spec = {"tokens": P(data_axis, None), "labels": P(data_axis, None)}

    def pipeline_loss(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        assert B % M == 0, (B, M)
        mb = B // M
        stage = lax.axis_index(stage_axis)
        positions = jnp.arange(S, dtype=jnp.int32)
        layer_stack = params[f"seg0"]["c0"]       # local [L/S, ...]

        tok_mb = tokens.reshape(M, mb, S)
        lab_mb = labels.reshape(M, mb, S)

        def stage_fn(x):
            def body(h, ps):
                h = _layer_fwd(cfg, spec, ps, h, positions)
                return h, None
            body = jax.checkpoint(body)
            x, _ = lax.scan(body, x, layer_stack)
            return x

        right = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            recv, loss_acc, denom = carry
            m_in = jnp.clip(t, 0, M - 1)
            inject = jnp.take(params["embed"], tok_mb[m_in], axis=0)
            if cfg.emb_scale:
                inject = inject * jnp.asarray(
                    float(cfg.d_model) ** 0.5, inject.dtype)
            x = jnp.where((stage == 0)[..., None, None, None]
                          if False else jnp.asarray(stage == 0),
                          inject.astype(recv.dtype), recv)
            y = stage_fn(x)

            # last stage: loss for microbatch m = t - (n_stages - 1)
            m_out = t - (n_stages - 1)
            valid = (stage == n_stages - 1) & (m_out >= 0) & (m_out < M)
            m_idx = jnp.clip(m_out, 0, M - 1)
            h = blocks.rms_norm(y, params["final_norm"], cfg.norm_eps)
            unembed = (params["embed"].T if cfg.tie_embeddings
                       else params["unembed"])
            logits = (h @ unembed.astype(h.dtype)).astype(jnp.float32)
            lab = lab_mb[m_idx]
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lab[..., None], -1)[..., 0]
            ce = jnp.sum(jnp.where(valid, logz - gold, 0.0))
            cnt = jnp.where(valid, jnp.asarray(lab.size, jnp.float32), 0.0)

            send = lax.ppermute(y, stage_axis, right)
            return (send, loss_acc + ce, denom + cnt), None

        recv0 = jnp.zeros((mb, S, cfg.d_model),
                          params["final_norm"].dtype)
        (_, loss_sum, denom), _ = lax.scan(
            tick, (recv0, jnp.zeros((), jnp.float32),
                   jnp.zeros((), jnp.float32)),
            jnp.arange(M + n_stages - 1))

        loss_sum = lax.psum(loss_sum, (data_axis, stage_axis))
        denom = lax.psum(denom, (data_axis, stage_axis))
        return loss_sum / jnp.maximum(denom, 1.0)

    p_specs = None  # resolved lazily per params tree

    def make_sharded_loss(params_tree):
        specs = jax.tree_util.tree_map_with_path(param_spec, params_tree)
        fn = jax.shard_map(
            pipeline_loss, mesh=mesh,
            in_specs=(specs, batch_spec), out_specs=P(),
            check_vma=False)
        return fn, specs

    def train_step(params, opt_state, batch, step):
        fn, _ = make_sharded_loss(params)
        loss, grads = jax.value_and_grad(fn)(params, batch)
        lr = lr_fn(step) if lr_fn else 1e-4
        params, opt_state, om = adamw.update(params, grads, opt_state, lr,
                                             adamw_cfg)
        return params, opt_state, {"loss": loss, **om}

    return train_step, make_sharded_loss, batch_spec
