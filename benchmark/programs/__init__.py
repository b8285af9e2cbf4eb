"""One adapter per application: how the benchmark makes its inputs from
the seed and calls the program's own entry point. Named by a
configuration's ``program`` key."""


def fold_key(seed: int):
    """A PRNG key from any whole number up to past 2**32."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )
