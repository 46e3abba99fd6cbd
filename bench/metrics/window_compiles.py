"""Backend compiles inside the window, counted from JAX's own
``/jax/core/compile/backend_compile_duration`` events; 0 when set-up
warmed every shape."""


def read(ctx):
    return float(ctx.compiles)
