"""Sum of jax's /jax/core/compile/* durations during set-up: trace,
lower, and backend compile or load from the persistent cache."""


def read(ctx):
    return ctx.get("compile_s")
