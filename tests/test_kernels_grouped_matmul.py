"""Grouped expert-FFN Pallas kernel (interpret mode) vs its oracle, and the
visit tables that drive its grid."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.grouped_matmul import grouped_ffn, reference
from repro.kernels.grouped_matmul.grouped_matmul import ff_tile, visit_tables

CASES = {
    # rows, d, ff, group sizes, row tile, dtype
    "one_tile": (40, 16, 24, [5, 0, 9, 3], 16, jnp.float32),
    "groups_span_tiles": (300, 64, 384, [100, 0, 37, 3], 32, jnp.float32),
    "bf16": (300, 64, 256, [100, 0, 37, 3], 32, jnp.bfloat16),
    "leading_empty_groups": (64, 32, 128, [0, 0, 17, 30], 16, jnp.float32),
    "all_rows_held_elsewhere": (20, 16, 24, [0, 0, 0, 0], 16, jnp.float32),
}


def _operands(m, d, ff, G, dtype, seed=0, layers=2):
    """Rows and a two-layer stack of G experts' weights."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (m, d)).astype(dtype)
    lead = (layers, G)
    wg = (jax.random.normal(ks[1], lead + (d, ff)) * d ** -0.5).astype(dtype)
    wu = (jax.random.normal(ks[2], lead + (d, ff)) * d ** -0.5).astype(dtype)
    wd = (jax.random.normal(ks[3], lead + (ff, d)) * ff ** -0.5).astype(dtype)
    return x, wg, wu, wd


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("name", sorted(CASES))
def test_grouped_ffn_matches_oracle(name, layer):
    """The rows of the groups equal the oracle's, for either layer of the
    stack; in float32 to rounding, in bf16 exactly (the kernel and the
    oracle round the same intermediates).  Rows past the groups are not
    computed: whatever the kernel leaves there, the oracle's are zero,
    and only the grouped rows are compared."""
    m, d, ff, sizes, tm, dtype = CASES[name]
    x, wg, wu, wd = _operands(m, d, ff, len(sizes), dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    at = jnp.asarray(layer, jnp.int32)
    out = grouped_ffn(x, wg, wu, wd, gs, at, tm=tm, interpret=True)
    want = reference(x, wg, wu, wd, gs, at)
    n = sum(sizes)
    assert out.shape == (m, d) and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out[:n], np.float32),
                               np.asarray(want[:n], np.float32),
                               rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(want[n:]))


def test_each_row_is_its_own_experts_ffn():
    """The oracle against one expert per row of layer 1, written out."""
    x, wg, wu, wd = _operands(12, 8, 16, 3, jnp.float32, seed=3)
    sizes = [4, 0, 6]
    out = reference(x, wg, wu, wd, jnp.asarray(sizes, jnp.int32), 1)
    group = np.repeat(np.arange(3), sizes)
    for r, g in enumerate(group):
        h = jax.nn.silu(x[r] @ wg[1, g]) * (x[r] @ wu[1, g])
        np.testing.assert_allclose(out[r], h @ wd[1, g], rtol=1e-5,
                                   atol=1e-6)
    assert not np.any(np.asarray(out[len(group):]))


def test_visit_tables():
    """Visits run group by group over the tiles each group's rows touch:
    a tile two groups share is visited once by each, empty groups and
    rows past the groups are not visited."""
    offsets, gids, tids, visits = visit_tables(
        jnp.asarray([5, 0, 20, 3], jnp.int32), m=64, tm=16)
    v = int(visits)
    assert list(np.asarray(offsets)) == [0, 5, 5, 25, 28]
    assert list(np.asarray(gids)[:v]) == [0, 2, 2, 3]
    assert list(np.asarray(tids)[:v]) == [0, 0, 1, 1]
    assert len(gids) == 64 // 16 + 4 - 1


def test_ff_tile():
    """DeepSeek-V2-Lite's expert width 1,408 is 11 x 128."""
    assert ff_tile(1408) == 128
    assert ff_tile(2048) == 512 and ff_tile(24) == 24
