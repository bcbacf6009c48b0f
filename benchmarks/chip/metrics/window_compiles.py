"""Backend compilations inside the window (jax.monitoring events)."""


def read(rec):
    return rec["compiles_in_window"]
