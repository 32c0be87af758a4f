"""The Pallas kernels at real widths, compiled for a described TPU v5e.

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
import re

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


def _grouped(rows):
    from repro.kernels.grouped_matmul import grouped_ffn
    # deepseek-v2-lite: 26 MoE layers of 16 held experts of 2048 -> 1408 ->
    # 2048; the rows of 24 decode lanes or of a 256-row chunk, 6 pairs each
    L, E, d, ff = 26, 16, 2048, 1408
    bf = jnp.bfloat16
    return grouped_ffn, [((rows, d), bf), ((L, E, d, ff), bf),
                         ((L, E, d, ff), bf), ((L, E, ff, d), bf),
                         ((E,), jnp.int32), ((), jnp.int32)], {}


CASES = {
    "paged_attention_mha": lambda: _paged(36),
    "paged_attention_gqa": lambda: _paged(4),
    "flash_attention_mha": lambda: _flash(36),
    "flash_attention_gqa": lambda: _flash(4),
    "ssd_scan": _ssd,
    "rglru_scan": _rglru,
    "grouped_matmul_decode": lambda: _grouped(24 * 6),
    "grouped_matmul_chunk": lambda: _grouped(256 * 6),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    op, args, kwargs = CASES[name]()
    sds = lambda s: jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
    compiled = op.lower(*map(sds, args),
                        **{k: sds(v) for k, v in kwargs.items()},
                        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


# -- the engine's paged steps: pools updated in place ------------------------

def _pool_ops(hlo: str, n_pages: int) -> list[str]:
    """HLO ``copy``, ``copy-start`` and ``convert`` instructions whose
    shape has ``n_pages`` (a page pool's page count) as a dimension."""
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = (.+?) (copy|copy-start|convert)\(",
                     line)
        if m and any(str(n_pages) in dims.split(",")
                     for dims in re.findall(r"\[([\d,]*)\]", m.group(1))):
            found.append(line.strip()[:160])
    return found


def _engine_programs(one_chip, cfg, kv_len: int, lanes: int):
    """``cfg`` served as the benchmark serves it (blocks of 16, chunks of
    256): the engine's own decode and chunk programs, built as on a chip
    (cache tree donated), compiled for one described v5e."""
    from repro.models import lm
    from repro.serve import engine as engine_mod

    donates = engine_mod._donates
    engine_mod._donates = lambda: True
    try:
        eng = engine_mod.ContinuousEngine(
            cfg, None, kv_len=kv_len, n_slots=lanes, dtype=jnp.bfloat16,
            paged=True, prefill_chunk=256, block_size=16)
    finally:
        engine_mod._donates = donates
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    tree = lambda t: jax.tree.map(sds, t)
    params = tree(jax.eval_shape(lambda: lm.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
    caches = tree(eng._caches)
    i32 = lambda *shape: sds(jax.ShapeDtypeStruct(shape, jnp.int32))
    lane_args = tree((eng._skeys, eng._temp, eng._topk, eng._topp))
    decode = eng._decode_prog.lower(
        params, caches, tree(eng._toks), tree(eng._pos), tree(eng._tables),
        i32(lanes), lane_args).compile()
    first = tree((eng._skeys[0], eng._temp[0], eng._topk[0], eng._topp[0]))
    chunk = eng._chunk.lower(
        params, caches, i32(1, 256), i32(), {"global": i32(kv_len // 16)},
        i32(), i32(), i32(), first).compile()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(eng._caches))
    n_pages = eng.allocator.config.n_blocks + 1
    return {"decode": decode, "chunk": chunk}, pool_bytes, n_pages


@pytest.fixture(scope="module")
def paged_steps(one_chip):
    """minicpm-2b at published widths in bf16, the depth cut to 4 layers,
    at the benchmark's 4 lanes x 2048 positions."""
    import dataclasses

    from repro.configs import get

    cfg = dataclasses.replace(get("minicpm-2b"), n_layers=4)
    return _engine_programs(one_chip, cfg, kv_len=2048, lanes=4)


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_paged_step_updates_the_pools_in_place(paged_steps, step):
    programs, pool_bytes, n_pages = paged_steps
    compiled = programs[step]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, (
        mem.alias_size_in_bytes, pool_bytes)
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes
    assert _pool_ops(compiled.as_text(), n_pages) == []


# -- MLA + MoE: the held experts behind the grouped matmul --------------------

@pytest.fixture(scope="module")
def moe_steps(one_chip):
    """deepseek-v2-lite at published widths in bf16, 4 layers (the dense
    one, then 3 MoE layers), holding 16 of the 64 experts, at the
    benchmark's 24 lanes x 4096 positions."""
    import dataclasses

    from repro.configs import get

    cfg = dataclasses.replace(get("deepseek-v2-lite-16b"), n_layers=4,
                              expert_shard=(0, 4))
    return _engine_programs(one_chip, cfg, kv_len=4096, lanes=24)


def _dispatch_shapes(hlo: str, experts: int, pairs: int) -> list[str]:
    """Instructions whose shape has an [experts, pairs] pair of dims, or
    experts x pairs rows: the lossless capacity dispatch's buffers."""
    found = []
    for line in hlo.splitlines():
        for dims in re.findall(r"\[([\d,]*)\]", line.split(" = ")[-1][:200]):
            d = [int(x) for x in dims.split(",") if x]
            if any(a == experts and b == pairs for a, b in zip(d, d[1:])) \
                    or any(x in (experts * pairs, experts * pairs + 1)
                           for x in d):
                found.append(line.strip()[:160])
    return found


def _memory_space_move(line: str) -> bool:
    """An async copy that only moves a buffer between memory spaces: its
    shapes and layouts are the same but for the ``S(n)`` space."""
    shapes = re.findall(r"(\w+\[[\d,]*\]\{[^}]*\})", line.split(" = ")[1])
    plain = {re.sub(r"S\(\d+\)", "", x) for x in shapes
             if not x.startswith("u32[]")}
    return re.search(r" copy-(start|done)\(", line) is not None \
        and len(plain) == 1


@pytest.mark.parametrize("step,rows", [("decode", 24), ("chunk", 256)])
def test_moe_step_runs_the_grouped_matmul_in_place(moe_steps, step, rows):
    """The held experts run in the grouped-matmul kernel (a Mosaic custom
    call), no [E, T*k, D] dispatch buffer is left, and the pools are
    aliased and never copied into another layout.  XLA may keep the dense
    layer's one-layer rope-key pool (25 MB) in on-chip memory across the
    decode step: a move between memory spaces, in the same layout.  The
    kernel reads each layer's experts from the stacked weights: no
    [16, 2048, 1408] slice of them is made."""
    programs, pool_bytes, n_pages = moe_steps
    compiled = programs[step]
    hlo = compiled.as_text()
    assert re.search(r"%grouped_matmul[.\d]* = .*custom_call_target="
                     r"\"tpu_custom_call\"", hlo)
    assert _dispatch_shapes(hlo, 16, rows * 6) == []
    assert not re.search(r"= bf16\[16,(2048,1408|1408,2048)\]", hlo)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert [x for x in _pool_ops(hlo, n_pages)
            if not _memory_space_move(x)] == []


def test_mla_decode_reads_the_pools_in_place(moe_steps):
    """The decode program attends through the ``mla_decode`` kernel, which
    reads each lane's pages of the layer from the stacked pools itself:
    no op holds the gathered ``[lanes x table width, 16, width]`` pages or
    one layer's pool sliced out of the stack or copied into another layout
    (a move between memory spaces, in the same layout, is let through as
    in the grouped-matmul test), and the program's
    temporaries stay under the 103,082,496 bytes the full-width gather
    needed (24 lanes x 256 pages)."""
    programs, _, n_pages = moe_steps
    compiled = programs["decode"]
    hlo = compiled.as_text()
    assert re.search(r"%mla_decode[.\d]* = .*custom_call_target="
                     r"\"tpu_custom_call\"", hlo)
    gathered = 24 * (4096 // 16)
    for line in hlo.splitlines():
        shape = line.split(" = ")[-1].split(" ")[0]
        assert not re.match(rf"bf16\[{gathered},16,(512|128)\]", shape), line
        assert not re.match(rf"bf16\[(1,)?{n_pages},16,(512|128)\]", shape) \
            or " dynamic-update-slice(" in line \
            or _memory_space_move(line) \
            or re.match(r"\s*%?\S+ = \S+ (parameter|get-tuple-element)\(",
                        line), line
    assert compiled.memory_analysis().temp_size_in_bytes < 103_082_496


def test_mha_decode_ignores_the_context_lens(paged_steps, one_chip,
                                             monkeypatch):
    """minicpm-2b's decode program compiles to the same ops, shapes
    included, whether or not ``lm.forward`` is handed the lanes' context
    lengths: only MLA layers read them."""
    import dataclasses

    from repro.configs import get
    from repro.models import lm
    from repro.serve import engine as engine_mod

    programs, _, _ = paged_steps
    forward = lm.forward
    monkeypatch.setattr(lm, "forward", lambda *a, context_lens=None, **k:
                        forward(*a, **k))
    monkeypatch.setattr(engine_mod, "_donates", lambda: True)
    cfg = dataclasses.replace(get("minicpm-2b"), n_layers=4)
    eng = engine_mod.ContinuousEngine(
        cfg, None, kv_len=2048, n_slots=4, dtype=jnp.bfloat16, paged=True,
        prefill_chunk=256, block_size=16)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    tree = lambda t: jax.tree.map(sds, t)
    params = tree(jax.eval_shape(lambda: lm.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
    plain = eng._decode_prog.lower(
        params, tree(eng._caches), tree(eng._toks), tree(eng._pos),
        tree(eng._tables), sds(jax.ShapeDtypeStruct((4,), jnp.int32)),
        tree((eng._skeys, eng._temp, eng._topk, eng._topp))).compile()

    def ops(compiled):
        found = re.findall(r"= (\S+) ([\w-]+)\(", compiled.as_text())
        return sorted((op, re.sub(r"\{[^}]*\}", "", shape))
                      for shape, op in found)

    assert ops(programs["decode"]) == ops(plain)
