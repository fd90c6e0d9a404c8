"""XLA compiles or persistent-cache loads inside the window (JAX's
backend-compile events), a count: a program the set-up did not warm.
Moves ``step_ms_p90``."""


def read(w):
    return float(w.compiles)
