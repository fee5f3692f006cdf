"""Backend compilations and persistent-cache loads counted between the
window's first and last job (a ``jax.monitoring`` listener the harness
registers).  A warm window reads 0."""


def read(rec):
    return rec.get("compiles")
