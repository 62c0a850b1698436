"""Seconds the backend spent compiling during set-up, net of the time
it spent reloading executables from the persistent cache (about 0 on a
warm cache). Source: jax.monitoring compile-duration events."""


def read(ctx):
    return ctx["setup"]["compiled_s"]
