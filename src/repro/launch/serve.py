"""Batched serving launcher — static batch or continuous batching.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --batch 4 --prompt-len 16 --max-new 32
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --continuous --requests 8 --stagger 2 --adapt --devices 4
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --continuous --paged --replicas 3 --disaggregate \
        --chunk-prefill 16 --shared-prefix 32 --requests 6

``--continuous`` drives the slot-scheduled engine over a staggered arrival
trace; ``--replicas N`` serves the same trace through a cache-aware router
over N engine replicas (``--disaggregate`` splits prefill from decode
replicas with block-granular KV handoff); ``--adapt`` then closes the
paper's compiler/assistant loop: the serving telemetry (slot occupancy,
cache pressure — fleet-aggregated under ``--replicas``) feeds the §3
scheduling assistants, which rebalance the compiler's plan under the
measured serving interference.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import Topology, adapt_plan, compile_plan
from repro.launch.device import device_info, enable_compile_cache
from repro.models import lm
from repro.serve import ContinuousEngine, Engine, Router, SamplingParams


def _trace(args, cfg, key):
    """The launcher's arrival trace: (prompt, frontend_emb, sampling) per
    request — shared between the single-engine and routed paths so
    ``--replicas`` changes placement, never the workload."""
    sp = None
    if args.temperature > 0:
        sp = [SamplingParams(temperature=args.temperature, top_k=args.top_k,
                             top_p=args.top_p, seed=args.sample_seed + i)
              for i in range(args.requests)]
    needs_fe = bool(cfg.frontend or cfg.n_enc_layers)
    shared = jax.random.randint(key, (max(0, args.shared_prefix),), 0,
                                cfg.vocab_size)
    out = []
    for i in range(args.requests):
        prompt = jax.random.randint(jax.random.fold_in(key, i),
                                    (args.prompt_len,), 0, cfg.vocab_size)
        if args.shared_prefix > 0:
            # every request opens with the same system-prompt-style prefix
            # — the workload the prefix cache deduplicates
            prompt = jnp.concatenate([shared, prompt])
        fe = (jax.random.normal(jax.random.fold_in(key, 10_000 + i),
                                (cfg.frontend_tokens, cfg.frontend_dim),
                                jnp.float32) if needs_fe else None)
        out.append((prompt, fe, None if sp is None else sp[i]))
    return out


def _router(args, cfg, params, key):
    """``--replicas N``: route the trace across an N-engine fleet, with
    ``--disaggregate`` splitting prefill from decode replicas."""
    plan = None
    if args.adapt:
        serve_shape = ContinuousEngine.decode_shape_for(args.kv_len,
                                                        args.batch)
        plan = compile_plan(cfg, serve_shape,
                            Topology.homogeneous(args.devices))
    router = Router.build(cfg, params, n_replicas=args.replicas,
                          disaggregate=args.disaggregate,
                          kv_len=args.kv_len, n_slots=args.batch,
                          paged=args.paged,
                          prefill_chunk=args.chunk_prefill,
                          prefix_cache=args.prefix_cache or None,
                          plans=plan,
                          dtype=jnp.float32 if args.reduced
                          else jnp.bfloat16,
                          bucket_prompts=args.bucket,
                          pricing=args.pricing,
                          cache_blocks=args.cache_blocks)
    if router.disagg_unsupported_reason:
        print(f"[router] {args.arch}: disaggregation unavailable "
              f"({router.disagg_unsupported_reason}) — running "
              f"{args.replicas} co-located replicas")
    for i, (prompt, fe, sp) in enumerate(_trace(args, cfg, key)):
        router.submit(prompt, max_new_tokens=args.max_new, rid=i,
                      arrival=i * args.stagger, frontend_emb=fe,
                      sampling=sp)
    t0 = time.time()
    results = router.run()
    dt = time.time() - t0
    fs = router.fleet_stats()
    total = fs["total_tokens"]
    roles = "/".join(r.role for r in router.replicas)
    print(f"[router] {args.arch}: {len(results)} requests over "
          f"{args.replicas} replicas ({roles}), {total} tokens in "
          f"{dt:.2f}s host wall-clock ({total / dt:.1f} tok/s)")
    print(f"[router] placement={fs['routed_per_replica']} "
          f"handoffs={fs['handoffs']} "
          f"transferred_blocks={fs['transferred_blocks']} "
          f"decode_starvation={fs['decode_starvation']} "
          f"occupancy={fs['occupancy']:.2f} "
          f"cache_pressure={fs['cache_pressure']:.2f}"
          + (f" prefix_hit_rate={fs['prefix_hit_rate']:.2f}"
             if args.prefix_cache or args.disaggregate else ""))
    for name, row in router.telemetry.summary().items():
        print(f"[router]   {name}: tokens={row['tokens']} "
              f"steps={row['steps']} "
              f"starved={row['decode_starvation']} "
              f"occupancy={row['occupancy']:.2f}")
    if results:
        print("first request:", results[0])
    if args.adapt:
        out = router.adapt()
        print(f"[adapt] fleet: {len(out.migrations)} queued-request "
              f"migrations, plan deltas="
              f"{len(out.trace.deltas) if out.trace else 0}")
        if out.trace and out.trace.deltas:
            print(f"[adapt] step time {out.trace.step_times[0]*1e3:.2f}ms "
                  f"-> {out.trace.step_times[-1]*1e3:.2f}ms "
                  f"({out.trace.improvement:.1%} under fleet load)")
    return router, results


def _static(args, cfg, params, key):
    eng = Engine(cfg, params, kv_len=args.kv_len,
                 dtype=jnp.float32 if args.reduced else jnp.bfloat16)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    fe = (jax.random.normal(key, (args.batch, cfg.frontend_tokens,
                                  cfg.frontend_dim), jnp.float32)
          if cfg.frontend else None)
    t0 = time.time()
    out = eng.generate(prompts, max_new_tokens=args.max_new, frontend_emb=fe)
    dt = time.time() - t0
    toks = args.batch * args.max_new
    print(f"[serve] {args.arch}: generated {out.shape} in {dt:.2f}s "
          f"host wall-clock ({toks/dt:.1f} tok/s batched)")
    print("first sequence:", out[0].tolist())
    return eng, out


def _continuous(args, cfg, params, key):
    plan = None
    if args.adapt:
        # compile (or fetch from the plan cache) the placement for the
        # decode traffic this launch actually serves: the engine's cache
        # length x lane count, not a hardcoded registry shape
        serve_shape = ContinuousEngine.decode_shape_for(args.kv_len,
                                                        args.batch)
        plan = compile_plan(cfg, serve_shape, Topology.homogeneous(args.devices))
    eng = ContinuousEngine(cfg, params, kv_len=args.kv_len,
                           n_slots=args.batch,
                           paged=args.paged,
                           bucket_prompts=args.bucket,
                           prefill_chunk=args.chunk_prefill,
                           prefix_cache=args.prefix_cache,
                           pricing=args.pricing,
                           cache_blocks=args.cache_blocks,
                           speculate=args.speculate,
                           draft_layers=args.draft_layers,
                           dtype=jnp.float32 if args.reduced else jnp.bfloat16,
                           plan=plan)
    # staggered arrivals: request i becomes admissible at step i * stagger;
    # per-request sampling (temperature 0 stays bitwise greedy) rides the
    # shared trace builder
    for i, (prompt, fe, sp) in enumerate(_trace(args, cfg, key)):
        eng.submit(prompt, max_new_tokens=args.max_new, rid=i,
                   arrival=i * args.stagger, frontend_emb=fe, sampling=sp)
    t0 = time.time()
    results = eng.run()
    dt = time.time() - t0
    tel = eng.telemetry
    total = sum(len(v) for v in results.values())
    print(f"[serve-cb] {args.arch}: {len(results)} requests, {total} tokens "
          f"in {dt:.2f}s host wall-clock ({total/dt:.1f} tok/s)")
    if not results:
        return eng, results
    print(f"[serve-cb] occupancy={tel.occupancy():.2f} "
          f"cache_pressure={tel.cache_pressure():.2f} "
          f"peak={tel.peak_cache_pressure():.2f} "
          f"step={tel.mean_step_ms():.1f}ms "
          f"slot_reuse={eng.scheduler.max_slot_reuse()} "
          f"prefill_compiles={eng.prefill_compiles()}")
    if args.paged:
        groups = tel.peak_resident_bytes_by_group()
        per_group = " ".join(f"{g}={b / 1024:.0f}KiB"
                             for g, b in sorted(groups.items()))
        print(f"[serve-cb] paged: peak_resident="
              f"{tel.peak_resident_bytes() / 1024:.0f}KiB / "
              f"{eng.allocator.capacity_bytes() / 1024:.0f}KiB "
              f"({len(eng.allocator.stores)} layer pools, "
              f"block_size={eng.block_size})"
              + (f" by_group: {per_group}" if per_group else ""))
    if args.prefix_cache:
        st = eng.allocator.prefix_stats()
        print(f"[serve-cb] prefix-cache: hit_rate="
              f"{tel.prefix_hit_rate():.2f} "
              f"({st['hit_tokens']}/{st['lookup_tokens']} tokens, "
              f"{st['hit_admissions']}/{st['admissions']} admissions) "
              f"commits={st['commits']} evictions={st['evictions']} "
              f"cow_forks={st['cow_forks']} "
              f"peak_shared={tel.peak_shared_saved_bytes() / 1024:.0f}KiB")
    if args.speculate:
        print(f"[serve-cb] speculative: k={args.speculate} "
              f"draft_layers={eng.draft_layers} "
              f"accept_rate={tel.accept_rate():.2f} "
              f"({tel.total_drafted()} drafted, "
              f"{tel.total_rewound_tokens()} rows rewound)")
    if eng.scheduler.preemptions:
        print(f"[serve-cb] preemptions={eng.scheduler.preemptions} "
              f"(lazy-pricing evict-and-requeue)")
    print("first request:", results[0])

    if args.adapt:
        # the engine's compiled plan models exactly the served decode shape
        # (engine.decode_shape()); the assistants emit typed PlanDelta
        # records that CompiledPlan.apply validates and replays
        assert plan is not None and plan.shape == eng.decode_shape()
        cb = tel.assistant_callback(plan.graph, plan.cost_model)
        adapted, trace = adapt_plan(
            plan, interference=tel.device_interference(plan.k), telemetry=cb)
        print(f"[adapt] plan {plan.describe()}"
              + (" (plan-cache hit)" if plan.from_cache else ""))
        print(f"[adapt] assistants: {len(trace.deltas)} deltas, step time "
              f"{trace.step_times[0]*1e3:.2f}ms -> "
              f"{trace.step_times[-1]*1e3:.2f}ms "
              f"({trace.improvement:.1%} improvement under serving load)")
        for d in trace.deltas:
            print(f"[adapt]   delta cycle={d.cycle} {d.node}: "
                  f"{d.src} -> {d.dst} ({d.resource}, "
                  f"gain {d.gain*1e3:+.2f}ms)")
        if trace.deltas:
            print(f"[adapt] adapted t_step {adapted.step_time*1e3:.2f}ms "
                  f"cut {adapted.cut_bytes:.3e}B (trace replayable: "
                  f"{adapted.assignment == trace.replay(plan.assignment)})")
    return eng, results


def main(argv=None):
    """Run one launch; returns ``(engine or router, results)``."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch size / continuous slot count")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--kv-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (slot scheduler + paged cache)")
    ap.add_argument("--requests", type=int, default=8,
                    help="continuous: number of requests in the trace")
    ap.add_argument("--stagger", type=int, default=2,
                    help="continuous: arrival gap between requests, in steps")
    ap.add_argument("--paged", action="store_true",
                    help="continuous: physical paged cache (block-table "
                         "decode; any arch — mixed layer groups: global "
                         "tables / window rings / recurrent state slots / "
                         "static enc-dec cross block sets)")
    ap.add_argument("--bucket", action="store_true",
                    help="continuous: pad prefills to power-of-two buckets "
                         "(bounds prefill compile count)")
    ap.add_argument("--chunk-prefill", type=int, default=0, metavar="C",
                    help="continuous+paged: prefill prompts in C-token "
                         "chunks interleaved with decode")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous+paged: content-addressed prefix-block "
                         "reuse with copy-on-write (decoder-only "
                         "global/MLA archs)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="P",
                    help="continuous: prepend the same P random tokens to "
                         "every prompt (the workload --prefix-cache "
                         "deduplicates)")
    ap.add_argument("--pricing", choices=("worst", "lazy"), default="worst",
                    help="continuous admission pricing: reserve the full "
                         "worst case (default) or oversubscribe and "
                         "preempt-requeue on mid-decode exhaustion")
    ap.add_argument("--cache-blocks", type=int, default=None, metavar="N",
                    help="continuous: override the self-sized block pool "
                         "(undersize it to exercise admission backpressure)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="continuous: sampling temperature (0 = exact "
                         "greedy argmax, the default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="continuous: keep only the k highest logits "
                         "(0 disables)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="continuous: nucleus sampling mass (1.0 disables)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="continuous: base PRNG seed for sampling (request "
                         "i uses sample-seed + i; --seed seeds the weights)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="continuous+paged: self-speculative decoding — "
                         "draft K tokens per round with a truncated-layer "
                         "pass, verify in one batched step, rewind the "
                         "paged cache past the rejection point")
    ap.add_argument("--draft-layers", type=int, default=None, metavar="L",
                    help="--speculate: layers the draft pass runs "
                         "(default: half the stack, whole cycles)")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="continuous: serve through a cache-aware router "
                         "over N engine replicas (N > 1)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="--replicas: replica 0 runs chunked prefill only "
                         "and hands finished KV blocks to decode replicas "
                         "(degrades to co-located on archs without "
                         "content-transferable blocks)")
    ap.add_argument("--adapt", action="store_true",
                    help="feed serve telemetry to the §3 assistants")
    ap.add_argument("--devices", type=int, default=4,
                    help="device count for --adapt planning")
    args = ap.parse_args(argv)

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"[device] {device_info()}")
    key = jax.random.PRNGKey(args.seed)
    params = lm.init_params(cfg, key, jnp.float32 if args.reduced
                            else jnp.bfloat16)
    if args.replicas > 1:
        if not args.continuous:
            raise SystemExit("--replicas requires --continuous (the router "
                             "fans a request trace over engine replicas)")
        return _router(args, cfg, params, key)
    if args.continuous:
        return _continuous(args, cfg, params, key)
    return _static(args, cfg, params, key)


if __name__ == "__main__":
    main()
