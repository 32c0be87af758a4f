"""Production training launcher.

Wires every substrate together: config -> planner (the paper's compiler) ->
sharding rules -> jit'd train step -> data pipeline -> checkpoint manager ->
telemetry + scheduling-assistant runtime.

On the CPU it runs reduced configs end to end (examples/ use it); on TPU
the same entry point runs published widths — ``--layers`` cuts depth, and
``--data-mesh``/``--model-mesh`` (or ``--multi-pod``) shard the state.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --layers 4 --steps 3 --data-mesh 2 --model-mesh 2   # four chips
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.core import Topology, compile_plan
from repro.core.placement import ShardingRules
from repro.data import DataConfig, make_pipeline
from repro.launch.device import device_info, enable_compile_cache
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import lm
from repro.models.config import ShapeConfig
from repro.optim import init_state, warmup_cosine, wsd
from repro.runtime.telemetry import Telemetry
from repro.train import TrainStepConfig, make_train_step


def main(argv=None):
    """Train; returns ``{"params", "opt", "losses"}`` after the last step
    (``losses`` maps step -> loss, read before that step's update)."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="cut depth to N layers, widths as published")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "wsd"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = cfg.replace(n_layers=args.layers)
    print(f"[device] {device_info()}")

    # --- the paper's compiler pass: plan the placement -----------------------
    # compile() goes through the on-disk plan cache, so re-launching the
    # same (config x shape x topology) reuses the stored artifact
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    k = max(args.model_mesh, 1)
    plan = compile_plan(cfg, shape, Topology.homogeneous(max(k, 2)),
                        backend="tensor")
    print(f"[plan] {plan.describe()}"
          + (" (plan-cache hit)" if plan.from_cache else ""))

    if args.multi_pod:
        mesh = make_production_mesh(multi_pod=True)
    else:
        mesh = make_mesh((args.data_mesh, args.model_mesh), ("data", "model"))
    rules = ShardingRules(mesh, fsdp=True)

    key = jax.random.PRNGKey(args.seed)
    dtype = jnp.float32 if args.reduced else jnp.bfloat16
    params = lm.init_params(cfg, key, dtype)
    opt = init_state(params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"[init] {args.arch} params={n_params/1e6:.1f}M dtype={dtype.__name__}")

    sched = (warmup_cosine if args.schedule == "cosine" else wsd)(
        args.lr, max(args.steps // 20, 2), args.steps)
    tcfg = TrainStepConfig(grad_accum=args.grad_accum,
                           n_groups=mesh.devices.size)
    step_fn, _ = make_train_step(cfg, sched, tcfg,
                                 shard_fn=rules.shard_fn(args.batch))

    with mesh:
        p_sh = rules.tree_shardings(rules.param_specs(params))
        o_sh = rules.tree_shardings(rules.opt_specs(opt))
        jit_step = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None, None),
                           out_shardings=(p_sh, o_sh, None),
                           donate_argnums=(0, 1))

        mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if mgr and args.resume and mgr.latest_step() is not None:
            state, meta = mgr.restore({"params": params, "opt": opt},
                                      shardings={"params": p_sh, "opt": o_sh})
            params, opt = state["params"], state["opt"]
            start = meta["step"]
            print(f"[resume] from step {start}")

        data = make_pipeline(
            DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch, seed=args.seed,
                       frontend_tokens=cfg.frontend_tokens if cfg.frontend else 0,
                       frontend_dim=cfg.frontend_dim if cfg.frontend else 0),
            start_step=start)
        telem = Telemetry()
        losses = {}

        for i in range(start, args.steps):
            step_i, raw = data.next() if hasattr(data, "next") else (i, data.batch_at(i))
            batch = {kk: jnp.asarray(vv) for kk, vv in raw.items()}
            t0 = time.time()
            params, opt, m = jit_step(params, opt, batch, jnp.asarray(step_i))
            losses[step_i] = float(m["loss"])
            dt = time.time() - t0
            telem.record(step_i, dt, losses[step_i])
            if step_i % args.log_every == 0 or step_i == args.steps - 1:
                print(f"[step {step_i:5d}] loss={losses[step_i]:.4f} "
                      f"gnorm={float(m['grad_norm']):.3f} "
                      f"lr={float(m['lr']):.2e} {dt*1e3:.0f}ms host "
                      f"wall-clock")
            if mgr and step_i and step_i % args.ckpt_every == 0:
                mgr.save(step_i, {"params": params, "opt": opt},
                         meta={"arch": args.arch})
        if mgr:
            mgr.save(args.steps, {"params": params, "opt": opt},
                     meta={"arch": args.arch})
        if hasattr(data, "close"):
            data.close()
    print(f"[done] median step {telem.median_ms():.0f}ms host wall-clock; "
          f"stragglers detected: {telem.n_stragglers()}")
    return {"params": params, "opt": opt, "losses": losses}


if __name__ == "__main__":
    main()
