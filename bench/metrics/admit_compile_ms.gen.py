"""Mean time, per admission of the measured window, that JAX spent
tracing, lowering, compiling and loading programs from the persistent
cache: over the program's ``serve.prefill`` spans, the union of their
``jax.*`` descendant spans. Every decoding request waits meanwhile. Moves
tpot_p99_ms."""

from bench.spans import compile_ms_per_prefill as read  # noqa: F401
