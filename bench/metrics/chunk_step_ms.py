"""Engine step / model: device time per prefill chunk program call, from
the trace."""


def read(ctx):
    return ctx.program_ms("jit_chunk_step")
