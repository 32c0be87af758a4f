"""Kernels (the grouped expert matmul): the least time the expert FFNs of
the traced steps could take at the chip's peaks, over the device time of
the trace's ops that carry the kernel's name.

Each traced step ran the decode program over its decoding lanes and one
chunk program per prompt chunk; the model's ``expert_cost`` gives the
FLOPs and bytes the grouped matmuls need for a program's tokens (the held
experts hit, read once; the rows routed to them).  A chunk counts the
cell's whole chunk width, its padded final rows included.

The trace keeps only its longest ops, so the kernel of one program can be
among them while the other's is not.  Each kernel op is matched to its
program by its rows, the program's tokens times the experts per token,
padded up: the chunk program's ``prefill_chunk`` tokens, the decode
program's ``lanes``.  A program's least time is counted only where its
kernel op is among the top ops.  None where the model has no experts or
neither program's kernel is among them."""

import re

KERNEL = "grouped_matmul"


def _rows(label: str) -> int | None:
    """'grouped_matmul.11 bf16[256,2048] custom-call' -> 256."""
    m = re.search(r"\[(\d+),", label)
    return int(m.group(1)) if m else None


def read(ctx):
    cost = getattr(ctx.model, "expert_cost", None)
    if ctx.trace is None or cost is None:
        return None
    serve = ctx.conf["serve"]
    k = ctx.model.dims(ctx.conf)["k"]
    unpadded = {"decode": serve["lanes"] * k,
                "chunk": serve["prefill_chunk"] * k}
    secs, seen = 0.0, set()
    for label, s in ctx.trace.top_ops:
        rows = _rows(label)
        if label.split(".")[0] != KERNEL or rows is None:
            continue
        fits = [p for p, r in unpadded.items() if r <= rows]
        if not fits:
            continue
        # the program with the most rows that pad up to this op's
        seen.add(max(fits, key=unpadded.get))
        secs += s
    if secs <= 0:
        return None
    least = 0.0
    for s in ctx.traced_steps():
        programs = []
        if "decode" in seen and s.decode_positions:
            programs.append(len(s.decode_positions))
        if "chunk" in seen:
            programs += [serve["prefill_chunk"]] * s.chunks
        for tokens in programs:
            f, b = cost(ctx.conf, tokens)
            least += max(f / ctx.peaks["bf16_flops_per_s"],
                         b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
