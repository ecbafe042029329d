"""Backend compile seconds during set-up, persistent-cache reads
included, from JAX's ``/jax/core/compile/backend_compile_duration``."""


def read(ctx):
    return ctx.compile_s
