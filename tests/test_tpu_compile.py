"""The four Pallas kernels at real widths, compiled for a described TPU v5e.

The TPU compiler is installed without a chip and compiles for a chip that
is described, not attached.  It refuses what interpret mode accepts (tiles
whose last two dims are neither (8, 128)-aligned nor whole, primitives
Mosaic does not implement), so these compiles guard the kernels' native
path at no chip time.  Nothing runs: results are checked on the chip by
``chip_smoke.py`` and in interpret mode by ``test_kernels_*.py``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers all import this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with the persistent compile cache off (a
    compile for a described chip is written there but cannot be read back
    without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _paged(kv):
    from repro.kernels.paged_attention import paged_attention
    # minicpm-2b: 36 heads of 64, 16-row pages, 8 lanes x 2048 positions
    B, H, hd, bs, W = 8, 36, 64, 16, 128
    n_pages = B * W + 1
    return paged_attention, [((B, H, hd), jnp.bfloat16),
                             ((n_pages, bs, kv, hd), jnp.bfloat16),
                             ((n_pages, bs, kv, hd), jnp.bfloat16),
                             ((B, W), jnp.int32), ((B,), jnp.int32)], {}


def _flash(kv):
    from repro.kernels.flash_attention import flash_attention
    B, S, H, hd = 1, 2048, 36, 64
    return flash_attention, [((B, S, H, hd), jnp.bfloat16),
                             ((B, S, kv, hd), jnp.bfloat16),
                             ((B, S, kv, hd), jnp.bfloat16)], {
        "q_positions": ((S,), jnp.int32), "k_positions": ((S,), jnp.int32)}


def _ssd():
    from repro.kernels.ssd_scan import ssd_scan
    # mamba2-370m: 32 heads of 64, state 128, chunk 256
    B, S, nh, hd, ns = 2, 1024, 32, 64, 128
    f32 = jnp.float32
    return ssd_scan, [((B, S, nh, hd), f32), ((B, S, nh), f32), ((nh,), f32),
                      ((B, S, ns), f32), ((B, S, ns), f32), ((nh,), f32)], {}


def _rglru():
    from repro.kernels.rglru_scan import rglru_scan
    # recurrentgemma-2b: width 2560
    B, S, W = 2, 1024, 2560
    return rglru_scan, [((B, S, W), jnp.float32),
                        ((B, S, W), jnp.float32)], {}


CASES = {
    "paged_attention_mha": lambda: _paged(36),
    "paged_attention_gqa": lambda: _paged(4),
    "flash_attention_mha": lambda: _flash(36),
    "flash_attention_gqa": lambda: _flash(4),
    "ssd_scan": _ssd,
    "rglru_scan": _rglru,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    op, args, kwargs = CASES[name]()
    sds = lambda s: jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
    compiled = op.lower(*map(sds, args),
                        **{k: sds(v) for k, v in kwargs.items()},
                        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
