"""Programs compiled or loaded from the compile cache inside the
measured window: JAX's backend-compile events between the window's
open and close. Warm-up covers every shape the cell's traffic uses, so
anything above zero is a shape (or a retrace) the window paid for."""


def read(run):
    return float(run.compiles)
