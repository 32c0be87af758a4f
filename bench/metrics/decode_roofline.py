"""Kernels (the decode program): the least time the bytes and FLOPs the
decode algorithm needs could take at the chip's peaks (weights once, each
active lane's resident K/V or state read once, its new rows written once),
over the measured device time of the decode program, for the steps the
trace holds."""


def read(ctx):
    if ctx.trace is None or "jit_decode_step" not in ctx.trace.programs:
        return None
    least = 0.0
    for s in ctx.traced_steps():
        if s.decode_positions:
            f, b = ctx.model.decode_cost(ctx.conf, s.decode_positions)
            least += max(f / ctx.peaks["bf16_flops_per_s"],
                         b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / ctx.trace.programs["jit_decode_step"][1]
