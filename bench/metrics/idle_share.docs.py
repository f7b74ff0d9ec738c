"""Percent of the traced window in which no op ran on the device. Moves
prompt_tokens_per_s."""

from bench.readers import idle_share as read  # noqa: F401
