"""Programs built inside the window (jax.monitoring compile requests,
cache hits included). None after a clean warm-up is the aim; 0 is a
count here, not a share, so it is reported."""


def read(ctx):
    return ctx.compiles_in_window
