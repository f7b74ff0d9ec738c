"""Programs compiled, or loaded from the persistent compilation cache,
inside the measured window (JAX's backend-compile events). Every one
stalls a prefill. Moves prompt_tokens_per_s."""

from bench.readers import compiles_in_window as read  # noqa: F401
