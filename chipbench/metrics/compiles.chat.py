"""Compilations and compile-cache loads inside the window (JAX's
monitoring events); every program should have been warmed in set-up."""


def read(ctx):
    return ctx.records.compiles
