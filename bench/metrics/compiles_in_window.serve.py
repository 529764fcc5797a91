"""Programs JAX built or loaded for the process inside the window (its
backend-compile events); warm-up should leave none."""


def read(run):
    return run.counters["compiles"]
