"""MLA decode Pallas kernel (interpret mode) against the paged MLA layer's
gather arithmetic and the float32 oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.kernels.mla_decode import mla_decode, reference
from repro.models import mla

BS = 16
LENGTHS = (0, 1, 15, 16, 17, 129)   # and W * BS, the whole table

CONFIGS = {
    # H = 4, kv_lora_rank 32, rope 8
    "tiny": lambda: get("deepseek-v2-lite-16b").reduced(),
    # H = 16, kv_lora_rank 512, rope 64: the published widths
    "published": lambda: get("deepseek-v2-lite-16b"),
}


def _operands(cfg, dtype, layers=3, width=12, seed=0):
    """A stack of ``layers`` latent pools; one lane per length of LENGTHS
    and one holding the whole table, each lane's table a shuffle of the
    pages with the null page past its live ones."""
    lens = np.asarray(LENGTHS + (width * BS,), np.int32)
    B, H, R = len(lens), cfg.n_heads, cfg.kv_lora_rank
    n_pages = B * width + 1
    null = n_pages - 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q_lat = jax.random.normal(ks[0], (B, H, R)).astype(dtype)
    q_rope = jax.random.normal(ks[1], (B, H, cfg.qk_rope_dim)).astype(dtype)
    ckv = jax.random.normal(ks[2], (layers, n_pages, BS, R)).astype(dtype)
    kr = mla.pad_krope(cfg, jax.random.normal(
        ks[3], (layers, n_pages, BS, cfg.qk_rope_dim))).astype(dtype)
    pages = np.asarray(jax.random.permutation(ks[4], null))
    tables = pages[:B * width].reshape(B, width)
    live = -(-lens // BS)
    tables = np.where(np.arange(width)[None] < live[:, None], tables, null)
    return (q_lat, q_rope, ckv, kr, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lens))


def _rel_err(out, want) -> float:
    """Largest absolute difference over the largest magnitude wanted."""
    return float(np.max(np.abs(out - want)) / np.max(np.abs(want)))


CASES = [(c, d, layer, pages) for c in CONFIGS
         for d in ("float32", "bfloat16") for layer in (0, 2)
         for pages in (1, 8)]


@pytest.mark.parametrize("config,dtype,layer,pages", CASES)
def test_kernel_matches_gather_and_oracle(config, dtype, layer, pages):
    """Every lane, lengths 0 to the whole table, through shuffled tables
    with null-page tails, for a layer of a three-layer stack: the kernel
    equals the float32 oracle (to rounding in float32; in bf16 to the
    rounding of its bf16 probabilities) and the paged MLA layer's gather
    arithmetic on every lane that reads a row.  A lane of length 0 gets
    finite zeros."""
    cfg = CONFIGS[config]()
    dt = jnp.dtype(dtype)
    q_lat, q_rope, ckv, kr, tables, lens = _operands(cfg, dt)
    scale = mla.softmax_scale(cfg)
    out = mla_decode(q_lat, q_rope, ckv, kr, tables, lens, layer,
                     scale=scale, pages=pages, interpret=True)
    assert out.shape == q_lat.shape and out.dtype == dt
    out = np.asarray(out, np.float32)
    want = np.asarray(reference(q_lat, q_rope, ckv, kr, tables, lens, layer,
                                scale=scale))
    tol = 1e-5 if dt == jnp.float32 else 2e-2
    assert _rel_err(out, want) <= tol

    lens_np = np.asarray(lens)
    assert np.all(np.isfinite(out))
    assert not np.any(out[lens_np == 0])

    # the gather path attends up to pos + 1 = lens; lanes reading rows
    pos = jnp.maximum(lens - 1, 0)
    gathered = mla._gathered_attend(
        cfg, q_lat[:, None], q_rope[:, None], ckv, kr, tables,
        jnp.int32(layer), pos + 1, pos[:, None], dt)[:, 0]
    got = np.asarray(gathered, np.float32)[lens_np > 0]
    assert _rel_err(out[lens_np > 0], got) <= tol


def test_oracle_is_softmax_over_the_live_rows():
    """The oracle against one lane's attention written out row by row."""
    cfg = CONFIGS["tiny"]()
    q_lat, q_rope, ckv, kr, tables, lens = _operands(cfg, jnp.float32)
    b, layer, n = 4, 1, 17                     # lane of length 17
    assert int(lens[b]) == n
    rows = np.asarray(ckv[layer, tables[b]]).reshape(-1, cfg.kv_lora_rank)
    keys = np.asarray(kr[layer, tables[b]]).reshape(-1, kr.shape[-1])
    s = (np.asarray(q_lat[b]) @ rows[:n].T +
         np.asarray(q_rope[b]) @ keys[:n, :cfg.qk_rope_dim].T)
    s = s * mla.softmax_scale(cfg)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = reference(q_lat, q_rope, ckv, kr, tables, lens, layer,
                     scale=mla.softmax_scale(cfg))
    np.testing.assert_allclose(np.asarray(want[b]), p @ rows[:n],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pages", (1, 8))
def test_kernel_reads_only_the_live_pages(pages):
    """Every pool row outside the lanes' live pages set to NaN — the null
    page, unused pages, other layers — leaves the kernel's output as it
    was: a last block's pages past a lane's length are never copied, and
    its buffer rows past them hold finite data."""
    cfg = CONFIGS["tiny"]()
    q_lat, q_rope, ckv, kr, tables, lens = _operands(cfg, jnp.float32)
    layer = 1
    live = np.zeros(ckv.shape[:2], bool)
    n_live = -(-np.asarray(lens) // BS)
    for b, t in enumerate(np.asarray(tables)):
        live[layer, t[:n_live[b]]] = True
    keep = jnp.asarray(live)[:, :, None, None]
    args = dict(scale=mla.softmax_scale(cfg), pages=pages, interpret=True)
    clean = mla_decode(q_lat, q_rope, ckv, kr, tables, lens, layer, **args)
    out = mla_decode(q_lat, q_rope, jnp.where(keep, ckv, jnp.nan),
                     jnp.where(keep, kr, jnp.nan), tables, lens, layer,
                     **args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
