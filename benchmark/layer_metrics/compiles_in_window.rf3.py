"""XLA backend compiles that ended inside the window (jax.monitoring, as
chip_smoke.CompileLog counts them); must read 0."""


def read(m):
    return m.compiles_in_window
