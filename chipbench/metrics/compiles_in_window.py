"""compiles_in_window: executables JAX created inside the window, compiled
or read from the persistent cache (its backend-compile events)."""


def read(run):
    return run.executables_in_window
