"""XLA executables compiled inside the window (jax.monitoring): the
paper's reconfiguration claim is that a new kernel compiles none."""


def read(r):
    return r.get("xla_compiles")
