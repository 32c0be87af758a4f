"""The expert roofline's reader on a slice of a chip run's trace of
``deepseek-v2-lite.chat``: the grouped matmul's device time against the
least time its work needs, and None where there is nothing to read."""

import gzip
import json
from types import SimpleNamespace

import pytest

from bench import spec, tracing
from bench_helpers import FIXTURES

PEAKS = spec.load_json(spec.BENCH_DIR / "peaks.json")["TPU v5 lite"]


def _recorded():
    """Whole engine steps of one traced window (about 0.8 s, op names
    shortened by ``op_label``) with the window span set to the slice, and
    each step's decoding positions and chunk count."""
    with gzip.open(FIXTURES / "trace-chat.json.gz") as f:
        return json.load(f)


def _ctx(events, steps, model="mla_moe"):
    cell = spec.load_cell("deepseek-v2-lite.chat")
    recs = [SimpleNamespace(decode_positions=d, chunks=c) for d, c in steps]
    return SimpleNamespace(
        trace=tracing.reduce(events), model=spec.load_model(model),
        conf=cell.config, peaks=PEAKS, traced_steps=lambda: recs)


def test_reads_the_grouped_matmul_against_its_least_time():
    rec = _recorded()
    ctx = _ctx(rec, rec["steps"])
    read = spec.load_reader("expert_roofline")
    kernel = [s for n, s in ctx.trace.top_ops
              if n.split(".")[0] == "grouped_matmul"]
    assert kernel, ctx.trace.top_ops
    least = 0.0
    for decode, chunks in rec["steps"]:
        for tokens in ([len(decode)] if decode else []) + [256] * chunks:
            f, b = ctx.model.expert_cost(ctx.conf, tokens)
            least += max(f / PEAKS["bf16_flops_per_s"],
                         b / PEAKS["hbm_bytes_per_s"])
    value = read(ctx)
    assert value == pytest.approx(100.0 * least / sum(kernel))
    assert 0.0 < value <= 100.0


DECODE_OP = "grouped_matmul.11 bf16[256,2048] custom-call"   # 24 x 6 rows
CHUNK_OP = "grouped_matmul.15 bf16[1536,2048] custom-call"   # 256 x 6 rows


@pytest.mark.parametrize("kept", [("decode", "chunk"), ("decode",),
                                  ("chunk",)])
def test_matches_each_kernel_op_to_its_program(kept):
    """Each kernel op is matched to its program by its rows, and a
    program's least time counts only where its op is among the top ops:
    a program whose kernel fell out of them adds neither time nor least
    time."""
    ops = {"decode": [DECODE_OP, 0.30], "chunk": [CHUNK_OP, 0.12]}
    steps = [([100, 200, 300], 0), ([101, 201, 301, 5], 2), ([], 1)]
    ctx = _ctx(_recorded(), steps)
    ctx.trace = SimpleNamespace(top_ops=[["fusion.475 bf16[6144,16,512] "
                                          "fusion", 1.7]]
                                + [ops[p] for p in kept])
    least = 0.0
    for decode, chunks in steps:
        tokens = (([len(decode)] if decode and "decode" in kept else [])
                  + ([256] * chunks if "chunk" in kept else []))
        for t in tokens:
            f, b = ctx.model.expert_cost(ctx.conf, t)
            least += max(f / PEAKS["bf16_flops_per_s"],
                         b / PEAKS["hbm_bytes_per_s"])
    secs = sum(ops[p][1] for p in kept)
    assert spec.load_reader("expert_roofline")(ctx) == pytest.approx(
        100.0 * least / secs)


def test_none_without_the_kernel_or_the_experts():
    rec = _recorded()
    read = spec.load_reader("expert_roofline")
    gone = {**rec, "devices": [
        {**d, "ops": [o for o in d["ops"]
                      if not o[0].startswith("grouped_matmul")]}
        for d in rec["devices"]]}
    assert read(_ctx(gone, rec["steps"])) is None
    dense = _ctx(rec, rec["steps"], model="dense")
    assert not hasattr(dense.model, "expert_cost") and read(dense) is None
    untraced = _ctx(rec, rec["steps"])
    untraced.trace = None
    assert read(untraced) is None
