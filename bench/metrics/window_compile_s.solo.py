"""Seconds of compilation inside the measured window (JAX's compile
events); set-up warms every program, so this should read 0."""


def read(ctx):
    return ctx.window_compile["compile_s"]
