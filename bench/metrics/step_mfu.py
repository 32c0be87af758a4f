"""Whole engine step: the FLOPs the window's steps needed (2 N per token
decoded or prefilled without the unembedding, the unembedding where logits
are read, attention or state work over the resident context) over the
steps' summed host-timed seconds times the chip's peak."""


def read(ctx):
    steps = ctx.window.window_steps()
    secs = sum(s.t1 - s.t0 for s in steps)
    if not steps or secs <= 0:
        return None
    flops = sum(s.flops for s in steps)
    return 100.0 * flops / (secs * ctx.peaks["bf16_flops_per_s"])
