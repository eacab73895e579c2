"""Programs JAX compiled, or loaded from its cache, between the window's first
and last instant. Should read 0: every shape is warmed up in set-up."""


def read(run):
    return run["compiles_in_window"]
