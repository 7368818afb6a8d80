"""Seconds of set-up spent compiling or loading programs from the
persistent cache (``jax.monitoring`` durations)."""


def read(ctx):
    return ctx.get("compile_setup_s")
