"""compiles_in_window: executables jax built or loaded from its cache while
the window ran (backend compiles plus persistent-cache hits)."""


def read(ctx):
    return float(ctx.compiles)
