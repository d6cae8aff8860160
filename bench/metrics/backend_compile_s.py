"""Seconds of set-up in the backend compiler, or reading its result back
from the persistent compilation cache (JAX's compile events)."""


def read(ctx):
    return ctx.setup_compile["backend_compile_s"]
