"""Backend compilations inside the window (JAX's compile listener): every
program the window runs has to be compiled in set-up."""


def read(ctx):
    return float(ctx["compiles_in_window"])
