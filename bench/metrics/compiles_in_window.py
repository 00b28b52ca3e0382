"""Compile: backend compiles from the window's opening until its last
answer (JAX monitoring events).  Set-up warms every shape, so it is 0."""


def read(ctx):
    return ctx.compiles_in_window
