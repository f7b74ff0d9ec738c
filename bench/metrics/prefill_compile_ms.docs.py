"""Mean time, per prefill of the measured window, that JAX spent tracing,
lowering, compiling and loading programs from the persistent cache: over
the program's ``serve.prefill`` spans, the union of their ``jax.*``
descendant spans. Moves prompt_tokens_per_s."""

from bench.spans import compile_ms_per_prefill as read  # noqa: F401
