"""setup_s: from the start of the process to the start of the window:
JAX start-up, fields made on the device, compile-cache loads (and
compiles in a run that compiles) and the warm-up of every shape the
window uses (host clock)."""


def read(run):
    return run.setup_s
