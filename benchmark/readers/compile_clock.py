"""Seconds JAX spent tracing, lowering and compiling (or loading from the
persistent cache) before the window opened, from its monitoring events."""


def read(ctx):
    return ctx.result["compile_s_at_open"]
