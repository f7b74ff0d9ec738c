"""Percent of the device's idle time in the traced window that lies under
the program's ``jax.*`` spans (tracing, lowering, compiling, loading from
the persistent cache). Moves prompt_tokens_per_s."""

from bench.spans import idle_compile_share as read  # noqa: F401
