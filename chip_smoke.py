#!/usr/bin/env python3
"""Bring-up smoke test: the serving path on a TPU chip, end to end.

    python chip_smoke.py             # one chip: phases (a) device,
                                     # (b) kernels, (c) minicpm-2b serving
    python chip_smoke.py --chips 4   # four chips: (a) device, then sharded
                                     # training checked against one device

One process holds the chip for the whole run and starts no other.  Any
failing phase raises, so the script exits non-zero; the last line of
standard output is the JSON result only when every phase passed.  There is
no CPU fallback: without a TPU, phase (a) exits non-zero.

Times printed on the way are host wall-clock times of this run, compilation
included (a cold run unless the compile cache already holds the programs).
They are not throughput numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.device import device_info, enable_compile_cache  # noqa: E402

ARCH = "minicpm-2b"

# Largest |kernel - oracle| allowed, relative to the oracle's largest |value|.
TOLERANCE = {
    # bf16 outputs carry 2^-8 relative rounding, and the oracle rounds its
    # probabilities to bf16 before the PV product while the kernels keep
    # them in f32 — the bound the interpret-mode bf16 sweeps use
    "paged_attention": 2e-2,
    "flash_attention": 2e-2,
    # f32 matmuls on the MXU may run as bf16 passes (2^-8 per product),
    # summed over a 256-row chunk and carried across chunks
    "ssd_scan": 1e-2,
    # f32 elementwise recurrence: only the association order differs from
    # the oracle's associative scan
    "rglru_scan": 1e-4,
}
# step-0 loss, sharded vs one device: bf16 weights and activations, with
# the reductions split across shards in a different order (|loss| ~ 12)
LOSS_TOLERANCE = 5e-2

# four chips: the train launcher at minicpm-2b's widths, depth cut so the
# launcher's one-device init of params + AdamW state (10 B/param) fits one
# chip: 8 layers are 0.77 B params, 7.7 GB
TRAIN_LAYERS = 8
TRAIN_MESH = (2, 2)                 # (data, model)
TRAIN_ARGV = ["--arch", ARCH, "--layers", str(TRAIN_LAYERS), "--steps", "3",
              "--batch", "8", "--seq", "256", "--log-every", "1",
              "--data-mesh", str(TRAIN_MESH[0]),
              "--model-mesh", str(TRAIN_MESH[1])]

SERVE_ARGV = ["--arch", ARCH, "--continuous", "--paged",
              "--chunk-prefill", "256", "--batch", "4", "--kv-len", "2048",
              "--prompt-len", "512", "--max-new", "32", "--requests", "8",
              "--stagger", "1"]


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def _require(ok: bool, what) -> None:
    # a raising check, not ``assert``: the checks must hold under ``-O`` too
    if not ok:
        raise SmokeFailure(what)


def _wall(t0: float) -> str:
    return f"{time.time() - t0:.1f}s host wall-clock"


def phase_device(chips: int) -> dict:
    """(a) Refuse to run anywhere but on enough TPU chips."""
    import jax
    print(f"[a] devices: {jax.devices()}", flush=True)
    info = device_info()
    print(f"[a] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        sys.exit(f"[a] no TPU: JAX found {info['platform']!r} devices")
    if info["count"] < chips:
        sys.exit(f"[a] {chips} chips requested, {info['count']} present")
    return info


def _check_native(name: str, fn, *args, **kw) -> None:
    """The op must lower to a Mosaic kernel, not to interpret-mode HLO."""
    text = fn.lower(*args, **kw).compile().as_text()
    _require("tpu_custom_call" in text, f"{name} did not run natively")


def _compare(name: str, out, ref) -> None:
    import jax.numpy as jnp
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    _require(out.shape == ref.shape, (name, out.shape, ref.shape))
    _require(bool(jnp.all(jnp.isfinite(out))), f"{name}: non-finite output")
    err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    tol = TOLERANCE[name]
    print(f"[b] {name}: shape={tuple(out.shape)} rel_err={err:.3e} "
          f"tol={tol:.0e}", flush=True)
    _require(err <= tol, f"{name}: rel_err {err} > {tol}")


def _flag(argv: list, name: str) -> int:
    return int(argv[argv.index(name) + 1])


def phase_kernels() -> None:
    """(b) The four Pallas kernels, native, against their oracles."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
    from repro.kernels.paged_attention import ops as pa_ops, ref as pa_ref
    from repro.kernels.rglru_scan import ops as rg_ops, ref as rg_ref
    from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref

    key = jax.random.PRNGKey(0)
    ks = list(jax.random.split(key, 16))
    bf16 = jnp.bfloat16

    # paged decode attention at minicpm-2b widths: 36 MHA heads of 64,
    # 16-row pages, 8 lanes with 2048-position tables
    t0 = time.time()
    B, H, hd, bs, W = 8, 36, 64, 16, 128
    n_pages = B * W + 1
    q = jax.random.normal(ks[0], (B, H, hd), bf16)
    kp = jax.random.normal(ks[1], (n_pages, bs, H, hd), bf16)
    vp = jax.random.normal(ks[2], (n_pages, bs, H, hd), bf16)
    tables = jax.random.permutation(ks[3], n_pages - 1)[:B * W]
    tables = tables.reshape(B, W).astype(jnp.int32)
    lens = jax.random.randint(ks[4], (B,), 1, W * bs + 1, jnp.int32)
    _check_native("paged_attention", pa_ops.paged_attention,
                  q, kp, vp, tables, lens)
    out = pa_ops.paged_attention(q, kp, vp, tables, lens)
    ref = pa_ref.reference(q[:, None], kp, vp, tables, lens,
                           q_positions=(lens - 1)[:, None])[:, 0]
    _compare("paged_attention", out, ref)
    del q, kp, vp, out, ref

    # causal flash attention at minicpm-2b widths over 2048 positions
    B, S = 1, 2048
    q, k, v = (jax.random.normal(ks[5 + i], (B, S, H, hd), bf16)
               for i in range(3))
    pos = jnp.arange(S, dtype=jnp.int32)
    _check_native("flash_attention", fa_ops.flash_attention, q, k, v,
                  q_positions=pos, k_positions=pos)
    out = fa_ops.flash_attention(q, k, v, q_positions=pos, k_positions=pos)
    ref = fa_ref.reference(q, k, v, q_positions=pos, k_positions=pos)
    _compare("flash_attention", out, ref)
    del q, k, v, out, ref

    # SSD scan at mamba2-370m widths: 32 heads of 64, state 128, chunk 256
    B, S, nh, hd, ns = 2, 1024, 32, 64, 128
    xs = jax.random.normal(ks[8], (B, S, nh, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[9], (B, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[10], (nh,)) * 0.3)
    Bm = jax.random.normal(ks[11], (B, S, ns)) / jnp.sqrt(ns)
    Cm = jax.random.normal(ks[12], (B, S, ns)) / jnp.sqrt(ns)
    D = jnp.ones((nh,))
    _check_native("ssd_scan", ssd_ops.ssd_scan, xs, dt, A, Bm, Cm, D)
    y, st = ssd_ops.ssd_scan(xs, dt, A, Bm, Cm, D)
    with jax.default_matmul_precision("highest"):
        ye, ste = ssd_ref.reference(xs, dt, A, Bm, Cm, D, chunk=256)
    _compare("ssd_scan", y, ye)
    _compare("ssd_scan", st, ste)

    # RG-LRU scan at recurrentgemma-2b's width 2560
    B, S, Wd = 2, 1024, 2560
    a = jax.nn.sigmoid(jax.random.normal(ks[13], (B, S, Wd)))
    bx = jax.random.normal(ks[14], (B, S, Wd))
    _check_native("rglru_scan", rg_ops.rglru_scan, a, bx)
    hs, hf = rg_ops.rglru_scan(a, bx)
    he, hfe = rg_ref.reference(a, bx)
    _compare("rglru_scan", hs, he)
    _compare("rglru_scan", hf, hfe)
    print(f"[b] kernels done in {_wall(t0)}", flush=True)


def phase_serve() -> None:
    """(c) minicpm-2b at published widths in bf16 through the launcher."""
    import jax
    from repro import configs
    from repro.launch import serve

    t0 = time.time()
    eng, results = serve.main(SERVE_ARGV)
    vocab = configs.get(ARCH).vocab_size
    n_req = _flag(SERVE_ARGV, "--requests")
    max_new = _flag(SERVE_ARGV, "--max-new")
    _require(sorted(results) == list(range(n_req)), sorted(results))
    for rid, toks in results.items():
        _require(len(toks) == max_new, (rid, len(toks)))
        _require(all(0 <= int(t) < vocab for t in toks), (rid, toks))
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[c] {n_req} requests x {max_new} tokens, all in [0, {vocab}); "
          f"steps={eng.now} prefill_compiles={eng.prefill_compiles()} "
          f"peak_resident_bytes={eng.telemetry.peak_resident_bytes()} "
          f"device_peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
          flush=True)
    print(f"[c] serving done in {_wall(t0)}", flush=True)


def phase_sharded_train() -> None:
    """Four chips: the train launcher's FSDP path on a mesh, its step-0
    loss against an unsharded forward on one device, and its state spread
    over every device."""
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch import train
    from repro.models import lm
    from repro.train.step import cross_entropy

    t0 = time.time()
    full = configs.get(ARCH)
    cfg = full.replace(n_layers=TRAIN_LAYERS)
    print(f"[t] {ARCH}: widths as published, depth cut to {TRAIN_LAYERS} of "
          f"{full.n_layers} layers: the launcher builds params and AdamW "
          f"state on one device before sharding them", flush=True)
    # the launcher's own seed and data stream, evaluated on one device
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    b0 = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=_flag(TRAIN_ARGV, "--seq"),
        global_batch=_flag(TRAIN_ARGV, "--batch"), seed=0)).batch_at(0)

    @jax.jit
    def loss(p, tokens, labels):
        logits, _, aux = lm.forward(cfg, p, tokens, mode="train")
        return cross_entropy(logits, labels) + aux

    ref = float(loss(params, jnp.asarray(b0["tokens"]),
                     jnp.asarray(b0["labels"])))
    del params
    out = train.main(TRAIN_ARGV)
    got = out["losses"][0]
    print(f"[t] step-0 loss sharded={got:.6f} one-device={ref:.6f} "
          f"|diff|={abs(got - ref):.3e} tol={LOSS_TOLERANCE:.0e}", flush=True)
    _require(abs(got - ref) <= LOSS_TOLERANCE, (got, ref))
    _require(all(jnp.isfinite(v) for v in out["losses"].values()),
             out["losses"])

    n_dev = TRAIN_MESH[0] * TRAIN_MESH[1]
    for name, leaf in (("params.embed", out["params"]["embed"]),
                       ("opt.m.embed", out["opt"]["m"]["embed"])):
        sh = leaf.sharding
        shard = sh.shard_shape(leaf.shape)
        print(f"[t] {name}: shape={leaf.shape} shard={shard} "
              f"devices={len(sh.device_set)}", flush=True)
        _require(len(sh.device_set) == n_dev, (name, sh))
        _require(shard != leaf.shape, (name, "replicated, not sharded"))
    print(f"[t] sharded training done in {_wall(t0)}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-training phase")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    import jax
    # persistent-cache reads (hits), writes (misses) and compile seconds
    # the hits saved, as JAX reports them
    cache = {"hits": 0, "misses": 0, "saved": 0.0}

    def on_event(event: str, **_) -> None:
        for k in ("hits", "misses"):
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1

    def on_duration(event: str, duration: float, **_) -> None:
        if event == "/jax/compilation_cache/compile_time_saved_sec":
            cache["saved"] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    t0 = time.time()
    info = phase_device(args.chips)
    if args.chips == 4:
        phase_sharded_train()
    else:
        phase_kernels()
        phase_serve()
    print(f"[cache] dir={cache_dir} hits={cache['hits']} "
          f"writes={cache['misses']} compile_saved={cache['saved']:.1f}s; "
          f"total {_wall(t0)}", flush=True)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
