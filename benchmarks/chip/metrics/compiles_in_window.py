"""Backend compiles (jax.monitoring) between the window's opening and
its close: set-up should leave none."""


def read(run):
    return run.rec.compiles_in_window
