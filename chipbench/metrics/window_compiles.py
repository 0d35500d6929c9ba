"""XLA executables built inside the window (JAX's backend-compile events)."""

def read(rec):
    return rec["compiles_in_window"]
