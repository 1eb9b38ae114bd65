"""Seconds of set-up spent getting programs ready, by JAX's own compile
events on the host clock: tracing, lowering, and compiling or loading
from the persistent cache."""


def read(ctx):
    return ctx.compile_s
