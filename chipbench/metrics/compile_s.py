"""Seconds of JAX compile events during set-up (tracing, lowering, backend
compile or persistent-cache read), from ``CompileClock``. Moves
``setup_s``."""


def read(ctx):
    return ctx.compile_s
