"""Seconds of set-up spent tracing and lowering to the compiler's input
(JAX's compile events: all compile time that is not the backend's)."""


def read(ctx):
    c = ctx.setup_compile
    return max(0.0, c["compile_s"] - c["backend_compile_s"])
