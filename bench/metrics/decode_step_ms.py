"""Engine step / model: device time per batched decode program call, from
the trace."""


def read(ctx):
    return ctx.program_ms("jit_decode_step")
