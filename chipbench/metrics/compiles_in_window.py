"""Compilation: programs XLA prepares inside the measured window (backend
compile events of a ``jax.monitoring`` listener, which fire also when the
persistent cache serves the program); 0 when set-up warmed every one."""


def read(ctx):
    return ctx.compiles
