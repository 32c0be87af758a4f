"""Set-up: process start to the first due request, compilation included."""


def read(ctx):
    return ctx.setup_s
