"""Programs compiled, or loaded from the persistent compilation cache,
inside the measured window (JAX's backend-compile events). Every one
stalls the loop. Moves tpot_p99_ms."""

from bench.readers import compiles_in_window as read  # noqa: F401
